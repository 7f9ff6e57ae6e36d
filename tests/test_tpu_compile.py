"""Compile the checkpoint-codec kernels for a described TPU v5e chip.

The interpret-mode tests (test_kernels.py, test_fused_codec.py) check
what the kernels compute; only the chip's compiler checks their block
shapes, memory spaces and VMEM use. These tests compile each kernel,
through its ops.py wrapper, at the sizes the snapshot path uses (a
16 MiB leaf in 256 KiB chunks) for a v5e chip that is described, not
attached, and assert the kernel is in the compiled program.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, so the
call must happen in the one test worker that runs this file.
"""
import importlib.util
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ckpt_codec import ops
from repro.kernels.ckpt_codec.ref import BLOCK, FP_CHUNK_BYTES

LEAF_BYTES = 16 << 20
CB = FP_CHUNK_BYTES
N_CHUNKS = LEAF_BYTES // CB
ROWS = LEAF_BYTES // (4 * BLOCK)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _leaf(dtype, sharding):
    n = LEAF_BYTES // jnp.dtype(dtype).itemsize
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


CASES = {
    "quantize": lambda s: ops.quantize.lower(
        _leaf(jnp.float32, s), interpret=False),
    "dequantize": lambda s: ops.dequantize.lower(
        _sds((ROWS, BLOCK), jnp.int8, s), _sds((ROWS,), jnp.float32, s),
        interpret=False),
    "xor": lambda s: ops._xor_i32.lower(
        _sds((ROWS, BLOCK), jnp.int32, s), _sds((ROWS, BLOCK), jnp.int32, s),
        interpret=False),
}
for _dt in (jnp.float32, jnp.bfloat16):
    _name = jnp.dtype(_dt).name
    CASES[f"fingerprint-{_name}"] = (
        lambda s, dt=_dt: ops._fingerprint_impl.lower(
            _leaf(dt, s), chunk_bytes=CB, interpret=False))
    for _cap in (8, 32):
        CASES[f"fused-{_name}-capacity{_cap}"] = (
            lambda s, dt=_dt, cap=_cap: ops._fused_capture_impl.lower(
                _leaf(dt, s), _sds((N_CHUNKS, 2), jnp.int32, s),
                chunk_bytes=CB, capacity=cap, interpret=False))


@pytest.mark.parametrize("case", sorted(CASES))
def test_codec_kernel_compiles_for_v5e(one_chip, case):
    compiled = CASES[case](one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_capacity_peaks_at_32_chunks():
    """The largest compaction buffer the snapshot path asks for at 256
    KiB chunks is the one compiled above: 32 chunks = the 8 MiB budget."""
    assert ops.fused_capacity(10 ** 6, CB, 10 ** 6) == 32


# --- names the trace reduction keys on -------------------------------------

BENCH = Path(__file__).resolve().parents[1] / "bench"


def _load(path: Path):
    name = "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("program,case", [
    ("jit__fingerprint_impl", "fingerprint-bfloat16"),
    ("jit__fused_capture_impl", "fused-bfloat16-capacity8")])
def test_capture_kernels_keep_the_names_the_trace_reduction_reads(
        one_chip, program, case):
    """The device trace names each op by its HLO text; the reduction
    (``bench/trace.py``) and ``capture_kernel_roofline`` find the capture
    kernels by the names of their ops and programs. A rename would turn
    the metric to null without an error."""
    trace = _load(BENCH / "trace.py")
    reader = _load(BENCH / "metrics" / "capture_kernel_roofline.py")
    text = CASES[case](one_chip).compile().as_text()
    assert text.startswith(f"HloModule {program},")
    kernels = {k[0] for k in map(trace.kernel_call, text.splitlines()) if k}
    assert kernels == {program[len("jit_"):]}
    assert kernels <= set(reader.KERNELS)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_engine_steps_keep_their_program_names(kind):
    """The breakdown's device time per program reads the engine's steps
    as ``jit_decode_fn`` and ``jit_prefill_fn``."""
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.serving import engine as E

    cfg = get_smoke_config("phi4-mini-3.8b")
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig(f"{kind}_s32_b2", 32, 2, kind)
    build = E.jit_decode_step if kind == "decode" else E.jit_prefill
    fn, _ = build(cfg, shape, mesh)
    specs = E.serve_input_specs(cfg, shape)
    params = M.init_abstract(cfg)
    args = ((params, specs["cache"], specs["tokens"], specs["pos"])
            if kind == "decode" else
            (params, specs["tokens"], specs["cache"]))
    text = fn.lower(*args).as_text()
    assert text.startswith(f"module @jit_{kind}_fn ")

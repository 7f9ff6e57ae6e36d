"""Checkpoint/restore smoke run on TPU at published widths.

    python chip_smoke.py             # one chip: codec kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: train on (2,2), restore (4,1)

One chip (the default) runs three phases in this one process:

* codec: every checkpoint-codec kernel, compiled for the chip, against
  its numpy twin in ``ckpt_codec/ref.py`` (bit-identical);
* serve: ``phi4-mini-3.8b`` (32L, d_model 3072, vocab 200064, bf16,
  params from ``--seed``) through ``ServingEngine.create`` under a
  ``CheckpointSession``, 8 slots x 2048 positions; two async snapshots
  mid-generation (a base, then a dirty-chunk delta link), restore into a
  fresh engine, finish; outputs token-identical to the run that was not
  interrupted;
* train: ``mamba2-780m`` through ``Trainer`` with an async snapshot
  (the fingerprint kernel records every leaf's baseline); restore into
  a fresh ``Trainer`` and continue; params digest and losses identical
  to the run that was not interrupted.

``--chips 4`` runs only the reshard phase: ``phi4-mini-3.8b`` trains on
a (2,2) mesh (its ~38 GB of bf16 params and f32 Adam moments need four
chips), takes a sharded async snapshot, restores onto a (4,1) mesh and
continues. Restored params are byte-equal to the saved ones; losses
match the run that was not interrupted within ``LOSS_RTOL``.

Snapshots go to ``--store-dir`` (default ``<checkout>/.smoke_store``),
one subdirectory per phase, deleted when the phase passes. The script
exits non-zero and prints no result unless JAX's devices are TPUs; its
last stdout line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARCH = "phi4-mini-3.8b"
TRAIN_ARCH = "mamba2-780m"
RESHARD_ARCH = "phi4-mini-3.8b"
AXES = ("data", "model")
# losses after a (2,2) -> (4,1) reshard differ by reduction order only
LOSS_RTOL = 2e-2


T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"{time.perf_counter() - T0:7.1f}s {msg}", flush=True)


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def peak_bytes() -> int:
    import jax
    return max(d.memory_stats()["peak_bytes_in_use"]
               for d in jax.local_devices())


def bytes_limit() -> int:
    import jax
    return min(d.memory_stats()["bytes_limit"] for d in jax.local_devices())


def capture_paths(stats) -> str:
    return (f"leaves through fingerprint kernel {stats['fp_kernel_leaves']}, "
            f"fused kernel {stats['fused_leaves']} (buffer overflowed to the "
            f"gather path {stats['fused_overflows']}), host fingerprint "
            f"{stats['host_fp_leaves']}; chunks dirty "
            f"{stats['dirty_chunks']}, clean {stats['clean_chunks']}")


def log_snapshots(tag: str, handles) -> None:
    for h in handles:
        log(f"[{tag}] snapshot at step {h.step}: stall (capture) "
            f"{h.timings['capture']:.3f}s, encode+commit "
            f"{h.timings['encode_commit']:.2f}s")


def codec_phase(seed: int) -> None:
    """Each codec kernel, compiled for this chip, vs its numpy twin."""
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from repro.kernels.ckpt_codec import ops, ref

    rng = np.random.RandomState(seed)
    cb = ref.FP_CHUNK_BYTES
    leaves = {"f32": rng.randn(3 * cb // 4 + 300).astype(np.float32),
              "bf16": rng.randn(2 * cb).astype(ml_dtypes.bfloat16)}
    for name, prev in leaves.items():
        cur = prev.copy()
        cur.view(np.uint8)[cb + 5] ^= 0x5A                  # dirty chunk 1
        pfp = ops.chunk_fingerprints(jnp.asarray(prev), cb)
        np.testing.assert_array_equal(np.asarray(pfp).view(np.uint32),
                                      ref.fingerprint_ref(prev, cb))
        fp, idx, data = ops.fused_dirty_chunk_capture(jnp.asarray(cur), pfp,
                                                      cb)
        rfp, count, ridx, rdata = ref.fused_capture_ref(cur, np.asarray(pfp),
                                                        cb)
        np.testing.assert_array_equal(np.asarray(fp).view(np.uint32), rfp)
        assert count == 1 and idx.tolist() == ridx.tolist() == [1], idx
        np.testing.assert_array_equal(data, rdata)
        log(f"[codec] {name} leaf of {prev.nbytes} B: fingerprint and fused "
            f"capture kernels bit-identical to ref")
    a = np.frombuffer(rng.bytes(3 << 20), np.uint8)
    b = np.frombuffer(rng.bytes(3 << 20), np.uint8)
    np.testing.assert_array_equal(ops.delta_encode(a, b),
                                  ref.delta_encode_ref(a, b))
    log("[codec] xor kernel bit-identical to ref")
    # the int8 codec's scale is amax / 127: the compiler may turn the
    # division into a multiply by the reciprocal, one ulp off numpy
    x = rng.randn(1000, 300).astype(np.float32)
    q, s = ops.quantize(jnp.asarray(x))
    rq, rs = ref.quantize_ref(x)
    q, s = np.asarray(q), np.asarray(s)
    np.testing.assert_allclose(s, rs, rtol=1e-6)
    off = np.abs(q.astype(np.int32) - rq).max()
    assert off <= 1, off
    np.testing.assert_allclose(np.asarray(ops.dequantize(q, s)),
                               ref.dequantize_ref(q, s), rtol=1e-6)
    log(f"[codec] quantize: scales within 1e-6 of ref, {int((q != rq).sum())} "
        f"of {q.size} codes one step off; dequantize within 1e-6")


def serve_phase(store: Path, *, arch: str = SERVE_ARCH, slots: int = 8,
                max_seq: int = 2048, prompt_len: int = 24, max_new: int = 24,
                snap_at=(6, 12), seed: int = 0) -> None:
    """Serve, snapshot twice mid-generation, restore the second snapshot
    into a fresh engine and finish: token-identical outputs."""
    import jax
    import numpy as np

    from repro.api import CheckpointSession, Policy
    from repro.configs import registry
    from repro.launch.common import restore_timings_line
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine

    cfg = registry.resolve_config(arch)
    spec = f"localfs:{store}"
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)))
    log(f"[serve] {arch}: {cfg.n_layers}L d_model {cfg.d_model} vocab "
        f"{cfg.vocab_size} {cfg.dtype}; params {gb(tree_bytes(params))} from "
        f"seed {seed} in {time.perf_counter() - t0:.2f}s")

    sess = CheckpointSession(spec, Policy(chain=2))
    eng = sess.attach(ServingEngine.create(
        arch, params, (1, 1), n_slots=slots, max_seq=max_seq,
        manager=sess.manager))
    log(f"[serve] {slots} slots x {max_seq} positions: kv cache "
        f"{gb(tree_bytes(eng.cache))}")
    rng = np.random.RandomState(seed)
    reqs = [Request(rid=i, max_new=max_new, prompt=rng.randint(
        0, cfg.vocab_size, size=prompt_len - i)) for i in range(slots)]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.step()
    jax.block_until_ready(eng.cache)
    log(f"[serve] first engine step ({slots} admissions + compile) "
        f"{time.perf_counter() - t0:.2f}s")
    handles, step_s = [], []
    while eng.queue or any(eng.slot_req):
        if eng.steps in snap_at:
            handles.append(sess.snapshot())
        t0 = time.perf_counter()
        eng.step()
        step_s.append(time.perf_counter() - t0)
    finished = {r.rid: list(r.out) for r in reqs}
    log(f"[serve] engine step median {np.median(step_s) * 1e3:.2f} ms over "
        f"{len(step_s)} steps (host clock, includes the token sync)")
    sess.wait()
    log_snapshots("serve", handles)
    log(f"[serve] capture: {capture_paths(sess.stats)}")
    sess.close()
    del eng, sess
    gc.collect()

    sess = CheckpointSession(spec)
    eng = sess.restore(step=snap_at[-1], expect_kind="serving", params=params)
    live = eng.live_requests()
    log(f"[serve] restored {len(live)} live sessions at engine step "
        f"{eng.steps} ({restore_timings_line(eng.incarnation)})")
    assert live and all(0 < len(r.out) < r.max_new for r in live), \
        "the restore point must be mid-generation"
    eng.run_until_drained()
    for r in live:
        assert r.out == finished[r.rid], (r.rid, r.out, finished[r.rid])
    log(f"[serve] {len(live)} restored sessions finished token-identical to "
        f"the run that was not interrupted")
    sess.close()


def _train(tag: str, sess, trainer, steps: int, snap_at,
           digest: bool = False) -> tuple:
    """Step ``trainer`` to ``steps`` with async snapshots at ``snap_at``;
    returns (losses, {step: params digest at that snapshot} if
    ``digest``)."""
    import numpy as np

    losses, handles, digests, step_s = [], [], {}, []
    for step in range(trainer.checkpoint_step() + 1, steps + 1):
        t0 = time.perf_counter()
        losses.append(trainer.train_steps(1)["loss"])
        step_s.append(time.perf_counter() - t0)
        if step in snap_at:
            handles.append(sess.snapshot())
            if digest:
                digests[step] = trainer.params_digest()
    log(f"[{tag}] first train step (compile + step) {step_s[0]:.2f}s; later "
        f"steps median {np.median(step_s[1:]):.3f}s (host clock, includes "
        f"the metrics sync); losses {losses}")
    sess.wait()
    log_snapshots(tag, handles)
    log(f"[{tag}] capture: {capture_paths(sess.stats)}")
    return losses, digests


def train_phase(store: Path, *, arch: str = TRAIN_ARCH,
                shape: str = "train_s512_b8", steps: int = 6,
                snap_at=(3,), seed: int = 0) -> None:
    """Train with async snapshots at ``snap_at``, restore the last into
    a fresh Trainer and continue: identical losses and params digest."""
    from repro.api import CheckpointSession, Policy
    from repro.launch.common import restore_timings_line
    from repro.train.loop import Trainer, TrainJob

    spec = f"localfs:{store}"
    sess = CheckpointSession(spec, Policy(chain=2))
    job = TrainJob(arch=arch, shape_key=shape, init_seed=seed)
    tr = sess.attach(Trainer(job, (1, 1), AXES, manager=sess.manager))
    t0 = time.perf_counter()
    tr.init_state()
    log(f"[train] {arch} {shape}: params "
        f"{gb(tree_bytes(tr.upper.get('params')))}, optimizer state "
        f"{gb(tree_bytes(tr.upper.get('opt_state')))}, init "
        f"{time.perf_counter() - t0:.2f}s")
    losses, _ = _train("train", sess, tr, steps, snap_at)
    digest = tr.params_digest()
    sess.close()
    del tr, sess
    gc.collect()

    sess = CheckpointSession(spec)
    tr = sess.restore(step=snap_at[-1], expect_kind="train")
    log(f"[train] restored at step {tr.checkpoint_step()} "
        f"({restore_timings_line(tr.incarnation)})")
    resumed = [tr.train_steps(1)["loss"] for _ in range(snap_at[-1], steps)]
    assert resumed == losses[snap_at[-1]:], (resumed, losses)
    assert tr.params_digest() == digest
    log(f"[train] resumed losses {resumed} and params digest {digest} "
        f"identical to the run that was not interrupted")
    sess.close()


def fit_check(arch: str, shape: str, mesh_shape, devices=None) -> int:
    """Compile the train step for ``mesh_shape`` and return the bytes one
    device needs for its arguments, outputs and temporaries."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.optim import abstract_opt_state
    from repro.train import step as step_lib

    cfg = registry.resolve_config(arch)
    mesh = make_mesh(mesh_shape, AXES, devices=devices)
    fn, info = step_lib.jit_train_step(cfg, registry.get_shape(shape), mesh)
    ab = M.init_abstract(cfg)
    compiled = fn.lower(ab, abstract_opt_state(ab, info["opt_cfg"]),
                        info["input_specs"],
                        jax.ShapeDtypeStruct((), jnp.int32),
                        jax.ShapeDtypeStruct((), jnp.float32)).compile()
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def reshard_phase(store: Path, *, arch: str = RESHARD_ARCH,
                  shape: str = "train_s256_b8", steps: int = 4,
                  snap_at: int = 2, mesh=(2, 2), target=(4, 1),
                  seed: int = 0) -> None:
    """Train on ``mesh``, snapshot the sharded state, restore onto
    ``target`` and continue: byte-equal params, losses within
    LOSS_RTOL of the run that was not interrupted."""
    import numpy as np

    from repro.api import CheckpointSession, Policy
    from repro.launch.common import restore_timings_line
    from repro.launch.mesh import make_mesh
    from repro.train.loop import Trainer, TrainJob

    limit = bytes_limit()
    for m in (mesh, target):
        need = fit_check(arch, shape, m)
        log(f"[reshard] {arch} {shape} train step on {m}: {gb(need)} per "
            f"chip of {gb(limit)}")
        assert need < limit, (m, need, limit)

    spec = f"localfs:{store}"
    sess = CheckpointSession(spec, Policy(chain=2))
    job = TrainJob(arch=arch, shape_key=shape, init_seed=seed)
    tr = sess.attach(Trainer(job, mesh, AXES, manager=sess.manager))
    tr.init_state()
    log(f"[reshard] training state {gb(tree_bytes(tr.upper.get('params')))} "
        f"params + {gb(tree_bytes(tr.upper.get('opt_state')))} optimizer, "
        f"sharded over {mesh}")
    losses, digests = _train("reshard", sess, tr, steps, (snap_at,),
                             digest=True)
    sess.close()
    del tr, sess
    gc.collect()

    sess = CheckpointSession(spec)
    tr = sess.restore(step=snap_at, expect_kind="train",
                      mesh_factory=lambda: make_mesh(target, AXES))
    assert tuple(tr.lower.mesh.shape.values()) == target, tr.lower.mesh
    log(f"[reshard] restored step {snap_at} onto {target} "
        f"({restore_timings_line(tr.incarnation)})")
    assert tr.params_digest() == digests[snap_at], "restored params differ"
    resumed = [tr.train_steps(1)["loss"] for _ in range(snap_at, steps)]
    ref = losses[snap_at:]
    rel = max(abs(a - b) / abs(b) for a, b in zip(resumed, ref))
    log(f"[reshard] params byte-equal after restore; losses on {target} "
        f"{resumed} vs {ref} on {mesh}: max relative difference {rel:.3e}")
    np.testing.assert_allclose(resumed, ref, rtol=LOSS_RTOL)
    sess.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: codec, serve and train phases; 4: the "
                         "(2,2) -> (4,1) reshard phase only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--store-dir", type=Path, default=ROOT / ".smoke_store")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips; JAX found {len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.common import use_compile_cache
    cache = use_compile_cache()
    shutil.rmtree(args.store_dir, ignore_errors=True)
    args.store_dir.mkdir(parents=True)
    log(f"[setup] {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{cache}; store {args.store_dir} "
        f"({gb(shutil.disk_usage(args.store_dir).free)} free)")

    if args.chips == 4:
        phases = [("reshard", lambda d: reshard_phase(d, seed=args.seed))]
    else:
        phases = [("codec", lambda d: codec_phase(args.seed)),
                  ("serve", lambda d: serve_phase(d, seed=args.seed)),
                  ("train", lambda d: train_phase(d, seed=args.seed))]
    for name, run in phases:
        t0 = time.perf_counter()
        run(args.store_dir / name)
        shutil.rmtree(args.store_dir / name, ignore_errors=True)
        gc.collect()
        log(f"[{name}] passed in {time.perf_counter() - t0:.1f}s; "
            f"peak_bytes_in_use so far {gb(peak_bytes())}")
    shutil.rmtree(args.store_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

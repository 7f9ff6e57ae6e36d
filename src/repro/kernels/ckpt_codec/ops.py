"""jit'd wrappers for the checkpoint codec kernels (padding, layout,
device dispatch; interpret mode off the TPU, for tests)."""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ckpt_codec import kernel as K
from repro.kernels.ckpt_codec.ref import BLOCK, FP_CHUNK_BYTES

# The backend never changes within a process, but jax.default_backend()
# re-resolves the platform stack on every call — and every new input
# shape retraces these jit wrappers, re-probing it. Resolve once.
_INTERPRET_DEFAULT: Optional[bool] = None


def _default_interpret() -> bool:
    global _INTERPRET_DEFAULT
    if _INTERPRET_DEFAULT is None:
        _INTERPRET_DEFAULT = jax.default_backend() != "tpu"
    return _INTERPRET_DEFAULT


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jax.Array, *, interpret: bool = None):
    """x: f32 any shape -> (q [nb, BLOCK] int8, scale [nb] f32)."""
    if interpret is None:
        interpret = _default_interpret()
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    xb = jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)
    nb = xb.shape[0]
    # pad rows so the tile divides evenly
    rows = min(K.ROWS_PER_TILE, nb)
    rpad = (-nb) % rows
    if rpad:
        xb = jnp.pad(xb, ((0, rpad), (0, 0)))
    q, s = K.quantize_blocks(xb, interpret=interpret)
    return q[:nb], s[:nb, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _xor_i32(a: jax.Array, b: jax.Array, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    nb = a.shape[0]
    rows = min(K.ROWS_PER_TILE, nb)
    rpad = (-nb) % rows
    if rpad:
        a = jnp.pad(a, ((0, rpad), (0, 0)))
        b = jnp.pad(b, ((0, rpad), (0, 0)))
    return K.xor_blocks(a, b, interpret=interpret)[:nb]


def delta_encode(x: np.ndarray, prev: np.ndarray, *,
                 interpret: bool = None) -> np.ndarray:
    """Byte XOR of two equal-length byte buffers through the Pallas
    kernel (TPU path of the chained snapshot encoder; the host path in
    core.delta uses numpy directly). Returns uint8[len]."""
    a = np.frombuffer(np.ascontiguousarray(x), np.uint8)
    b = np.frombuffer(np.ascontiguousarray(prev), np.uint8)
    assert a.size == b.size, (a.size, b.size)
    n = a.size
    lane_bytes = 4 * BLOCK
    pad = (-n) % lane_bytes
    if pad:
        a = np.concatenate([a, np.zeros(pad, np.uint8)])
        b = np.concatenate([b, np.zeros(pad, np.uint8)])
    ai = jnp.asarray(a.view(np.int32).reshape(-1, BLOCK))
    bi = jnp.asarray(b.view(np.int32).reshape(-1, BLOCK))
    out = np.asarray(jax.device_get(_xor_i32(ai, bi, interpret=interpret)))
    return out.view(np.uint8).reshape(-1)[:n]


def delta_decode(delta: np.ndarray, prev: np.ndarray, dtype,
                 shape, *, interpret: bool = None) -> np.ndarray:
    """XOR is its own inverse; reinterpret the result. ``interpret``
    is forwarded to the encode kernel (a CPU caller forcing
    ``interpret=True`` must not silently get the probed default)."""
    raw = delta_encode(delta, prev, interpret=interpret)
    return np.frombuffer(raw.tobytes(), dtype=dtype).reshape(shape)


# ---------------------------------------------------------------------------
# dirty-chunk fingerprints + device-side gather compaction
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk_bytes", "interpret"))
def _fingerprint_impl(x: jax.Array, *, chunk_bytes: int,
                      interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    fp = K.fingerprint_blocks(_device_chunks(x, chunk_bytes),
                              interpret=interpret)
    return fp[:, 0, :2]


def _device_chunks(x: jax.Array, chunk_bytes: int) -> jax.Array:
    """View a device array as [n_chunks, rows, BLOCK] of its own dtype
    (zero-padded to a chunk multiple) without leaving the device. The
    kernels read the raw bits themselves: a bitcast here would be a
    separate pass over the leaf, and regrouping narrow elements into i32
    words would give a [n, k] array whose minor dim the TPU pads to a
    full 128-lane tile."""
    flat = x.reshape(-1)
    size = flat.dtype.itemsize
    pad = (-flat.size) % (chunk_bytes // size)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, chunk_bytes // (size * BLOCK), BLOCK)


def chunk_fingerprints(x, chunk_bytes: int = FP_CHUNK_BYTES, *,
                       interpret: bool = None) -> jax.Array:
    """Per-chunk fingerprints of a (device or host) array through the
    Pallas kernel: i32 [n_chunks, 2]. The leaf is read once on device;
    only the fingerprints are small enough to compare/keep resident.
    chunk_bytes must be a multiple of 4*BLOCK (one i32 lane row)."""
    assert chunk_bytes % (4 * BLOCK) == 0, chunk_bytes
    return _fingerprint_impl(jnp.asarray(x), chunk_bytes=chunk_bytes,
                             interpret=interpret)


@jax.jit
def _dirty_mask(fp: jax.Array, prev_fp: jax.Array) -> jax.Array:
    return jnp.any(fp != prev_fp, axis=1)


@functools.partial(jax.jit, static_argnames=("chunk_bytes",))
def _gather_chunks(x: jax.Array, idx: jax.Array, *, chunk_bytes: int):
    return jnp.take(_device_chunks(x, chunk_bytes), idx, axis=0)


def dirty_chunk_capture(x, prev_fp, chunk_bytes: int = FP_CHUNK_BYTES, *,
                        interpret: bool = None
                        ) -> Tuple[jax.Array, np.ndarray, Optional[np.ndarray]]:
    """Device-side incremental capture of one leaf — the two-launch
    path (fingerprint launch, mask sync, gather launch, payload sync).
    Kept as the explicit fallback for :func:`fused_dirty_chunk_capture`
    (compaction-buffer overflow) and for callers that cannot bound the
    dirty count up front; the pipeline's default is the fused kernel.

    Fingerprints ``x`` on device, compares against the previous
    snapshot's device-resident fingerprints, gather-compacts the dirty
    chunks on device, and returns
    ``(new_fp [device], dirty_idx [host i64], dirty_bytes [host u8
    [k, chunk_bytes] or None])`` — the data makes exactly one
    device->host hop, sized by what changed rather than by the leaf.

    The gather index vector is padded to the next power of two so jit
    retraces O(log n_chunks) variants, not one per dirty count.
    """
    fp = chunk_fingerprints(x, chunk_bytes, interpret=interpret)
    mask = np.asarray(jax.device_get(_dirty_mask(fp, prev_fp)))
    idx = np.nonzero(mask)[0]
    if idx.size == 0:
        return fp, idx, None
    padded = 1 << (idx.size - 1).bit_length()
    idxp = np.full(padded, idx[-1], np.int32)
    idxp[:idx.size] = idx
    compact = _gather_chunks(jnp.asarray(x), jnp.asarray(idxp),
                             chunk_bytes=chunk_bytes)
    host = np.asarray(jax.device_get(compact))[:idx.size]
    return fp, idx, host.view(np.uint8).reshape(idx.size, chunk_bytes)


# ---------------------------------------------------------------------------
# fused single-pass capture (fingerprint + compare + compact, one launch)
# ---------------------------------------------------------------------------

# the compaction buffer stays VMEM-resident for the whole grid (constant
# index map), so its size is bounded; 8 MB leaves room for the input
# chunk tile + fingerprints inside a 16 MB VMEM
_FUSED_VMEM_BUDGET = 8 << 20
# capacity floor: below this the pow-of-two bucketing would retrace the
# jit wrapper for every tiny dirty-count fluctuation
_FUSED_MIN_CAPACITY = 8


def fused_capacity(n_chunks: int, chunk_bytes: int,
                   hint: Optional[int] = None) -> int:
    """Compaction-buffer capacity (in chunks) for one fused launch.

    2x the caller's hint (the leaf's dirty count last snapshot — change
    rates are stable step to step), clamped to the leaf and to the VMEM
    budget, then rounded up to a power of two so jit retraces O(log)
    capacity variants instead of one per dirty count."""
    cap = max(_FUSED_MIN_CAPACITY,
              2 * (hint if hint is not None else _FUSED_MIN_CAPACITY))
    cap = min(cap, n_chunks, max(1, _FUSED_VMEM_BUDGET // chunk_bytes))
    return 1 << (cap - 1).bit_length()


@functools.partial(jax.jit,
                   static_argnames=("chunk_bytes", "capacity", "interpret"))
def _fused_capture_impl(x: jax.Array, prev_fp: jax.Array, *,
                        chunk_bytes: int, capacity: int,
                        interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    # previous fingerprints enter in the kernel's lane-dense row layout
    pfp = jnp.pad(prev_fp, ((0, 0), (0, K.FP_LANES - 2)))[:, None, :]
    fp, cnt, idx, compact = K.fused_capture_blocks(
        _device_chunks(x, chunk_bytes), pfp, capacity,
        interpret=interpret)
    return fp[:, 0, :2], cnt, idx, compact


def fused_dirty_chunk_capture(
        x, prev_fp, chunk_bytes: int = FP_CHUNK_BYTES, *,
        capacity_hint: Optional[int] = None, interpret: bool = None,
        to_host: Optional[Callable] = None
        ) -> Tuple[jax.Array, np.ndarray, Optional[np.ndarray]]:
    """Single-pass incremental capture of one leaf: exactly ONE kernel
    launch and ONE blocking device->host transfer.

    The fused kernel reads the leaf once, computes the 2-lane chunk
    fingerprints, compares them in-kernel against the device-resident
    previous fingerprints, and prefix-sum-compacts the dirty chunks into
    a bounded buffer; ``(count, idx, compact)`` come back in one
    ``device_get``. Returns the same ``(new_fp [device], dirty_idx
    [host i64], dirty_bytes [host u8 [k, chunk_bytes] or None])``
    contract as :func:`dirty_chunk_capture`, which remains the explicit
    fallback: when more than ``capacity`` chunks are dirty (the kernel
    keeps counting past the buffer so the host can tell), the gather
    path finishes the job, reusing the fingerprints already computed.

    ``capacity_hint`` sizes the compaction buffer (chunks dirty last
    snapshot); see :func:`fused_capacity` for the clamping policy.
    ``to_host`` makes every blocking device->host copy (a caller that
    accounts for its transfers passes its own; ``jax.device_get`` if
    None).
    """
    assert chunk_bytes % (4 * BLOCK) == 0, chunk_bytes
    to_host = to_host or jax.device_get
    xd = jnp.asarray(x)
    if isinstance(prev_fp, np.ndarray):  # ref-twin callers hold u32
        prev_fp = prev_fp.view(np.int32)
    n_chunks = -(-xd.nbytes // chunk_bytes)
    capacity = fused_capacity(n_chunks, chunk_bytes, capacity_hint)
    fp, cnt, idx, compact = _fused_capture_impl(
        xd, prev_fp, chunk_bytes=chunk_bytes, capacity=capacity,
        interpret=interpret)
    # the one blocking hop: count + indices + compacted payload together
    cnt_h, idx_h, compact_h = to_host((cnt, idx, compact))
    k = int(cnt_h[0])
    if k == 0:
        return fp, np.empty(0, np.int64), None
    if k > capacity:
        # overflow: the change rate outran the buffer. Finish via the
        # two-launch gather fallback, reusing the fingerprints (costs
        # the old path's extra sync — but only on the rare step whose
        # dirty count more than doubled; the caller's next hint is k)
        mask = np.asarray(to_host(_dirty_mask(fp, prev_fp)))
        full_idx = np.nonzero(mask)[0]
        padded = 1 << (full_idx.size - 1).bit_length()
        idxp = np.full(padded, full_idx[-1], np.int32)
        idxp[:full_idx.size] = full_idx
        gathered = _gather_chunks(xd, jnp.asarray(idxp),
                                  chunk_bytes=chunk_bytes)
        host = np.asarray(to_host(gathered))[:full_idx.size]
        return (fp, full_idx.astype(np.int64),
                host.view(np.uint8).reshape(full_idx.size, chunk_bytes))
    dirty_idx = idx_h[:k].astype(np.int64)
    dirty_bytes = np.ascontiguousarray(compact_h[:k]) \
        .view(np.uint8).reshape(k, chunk_bytes)
    return fp, dirty_idx, dirty_bytes


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize(q: jax.Array, scale: jax.Array, *, interpret: bool = None):
    if interpret is None:
        interpret = _default_interpret()
    nb = q.shape[0]
    rows = min(K.ROWS_PER_TILE, nb)
    rpad = (-nb) % rows
    if rpad:
        q = jnp.pad(q, ((0, rpad), (0, 0)))
        scale = jnp.pad(scale, (0, rpad))
    x = K.dequantize_blocks(q, scale[:, None], interpret=interpret)
    return x[:nb].reshape(-1)

"""Pallas TPU kernels of the checkpoint codec: int8 block quantization,
XOR delta, per-chunk fingerprints and the fused dirty-chunk capture.

Why kernels: snapshotting a model's state through the codec is
HBM-bandwidth-bound; fusing abs-max + scale + round into one VMEM pass
reads each element once (vs 3 passes for the naive composition), and
the fingerprint kernels read a leaf once so only 8 B per chunk (or the
dirty chunks themselves) ever leave the device.

Tiling: rows of 256-lane blocks. Quantize and XOR process
(ROWS_PER_TILE, 256) tiles. The fingerprint kernels view a leaf, in its
own dtype, as [n_chunks, rows_per_chunk, 256] and take one chunk per
grid step; a
block whose last two dims equal the array's satisfies the TPU's (8, 128)
tiling rule for any chunk size. Per-chunk fingerprints travel as one
lane-dense (1, FP_LANES) row per chunk (lanes 0 and 1 hold the two
hashes, the rest are zero), because a (1, 2) block is not a legal TPU
tile; ops.py slices the [n_chunks, 2] view out of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 256
ROWS_PER_TILE = 512
# width of one chunk's fingerprint row (one vreg lane row)
FP_LANES = 128

# same-width integer views: zero-extends narrow elements' raw bits
_UINT_OF_SIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.int32}

# fingerprint mixing constants — shared with ref.fingerprint_ref
_FP_XOR_C = 0x5BD1E995
_FP_MUL_C = 0x9E3779B1 - (1 << 32)  # as signed int32


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...]                                       # [R, BLOCK] f32
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)    # [R, 1]
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def quantize_blocks(xb: jax.Array, *, interpret: bool = False):
    """xb: f32 [nb, BLOCK] (padded by ops.py) -> (q int8 [nb, BLOCK],
    scale f32 [nb, 1]). The scale is a column so its block tiles like
    the rows it scales."""
    nb = xb.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    assert nb % rows == 0, (nb, rows)
    grid = (nb // rows,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xb)


def _xor_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jax.lax.bitwise_xor(a_ref[...], b_ref[...])


def xor_blocks(a: jax.Array, b: jax.Array, *, interpret: bool = False):
    """Byte-level XOR delta for chained snapshots, vectorized as int32
    lanes: a, b are [nb, BLOCK] int32 views of the raw payload (ops.py
    does the byte reinterpretation + padding). One VMEM pass, pure
    VPU work — HBM-bandwidth-bound like the quantizer."""
    nb = a.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    assert nb % rows == 0, (nb, rows)
    grid = (nb // rows,)
    return pl.pallas_call(
        _xor_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), jnp.int32),
        interpret=interpret,
    )(a, b)


def _fp_row(x):
    """Two positional multiply-mix hashes over one chunk, returned as a
    (1, FP_LANES) row: h1 in lane 0, h2 in lane 1.

    ``x`` holds the chunk's elements, of width 1, 2 or 4 bytes; the hash
    reads their raw bits. The hashes are defined over the chunk's
    little-endian
    int32 words (``ref.fingerprint_ref``). Both are linear in the bytes
    of a word, and XOR acts on each byte field alone, so element ``m``
    adds its own term at word ``m // k`` and bit offset ``8 * s * (m %
    k)`` (``k`` elements of ``s`` bytes per word). That gives the same
    int32-wrapping sums as building the words, which on the TPU would
    need a lane shuffle."""
    s = x.dtype.itemsize
    k = 4 // s
    r, c = x.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
    m = row * c + col                                # element within chunk
    e = jax.lax.bitcast_convert_type(x, _UINT_OF_SIZE[s]).astype(jnp.int32)
    xor_c = jnp.int32(_FP_XOR_C)
    if k == 1:
        w1, w2 = 2 * m + 1, 2 * m + jnp.int32(_FP_MUL_C)
    else:
        word = m // k
        shift = (m % k) * (8 * s)
        w1 = (2 * word + 1) << shift
        w2 = (2 * word + jnp.int32(_FP_MUL_C)) << shift
        xor_c = (xor_c >> shift) & ((1 << (8 * s)) - 1)
    h1 = jnp.sum(e * w1)
    h2 = jnp.sum((e ^ xor_c) * w2)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, FP_LANES), 1)
    return jnp.where(lane == 0, h1, jnp.where(lane == 1, h2, 0))


def _chunk_spec(rows_per_chunk: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, rows_per_chunk, BLOCK), lambda i: (i, 0, 0))


_FP_SPEC = pl.BlockSpec((1, 1, FP_LANES), lambda i: (i, 0, 0))


def _fingerprint_kernel(x_ref, o_ref):
    o_ref[0] = _fp_row(x_ref[0])


def fingerprint_blocks(xc: jax.Array, *, interpret: bool = False):
    """xc: [n_chunks, rows_per_chunk, BLOCK] of a 1-, 2- or 4-byte dtype
    (padded by ops.py) -> i32 [n_chunks, 1, FP_LANES] fingerprint rows.

    One VMEM pass per chunk: the whole leaf is read once at HBM
    bandwidth and only the fingerprints ever leave the device — dirty
    detection without a device->host copy of the data."""
    n_chunks, rows, _ = xc.shape
    return pl.pallas_call(
        _fingerprint_kernel,
        grid=(n_chunks,),
        in_specs=[_chunk_spec(rows)],
        out_specs=_FP_SPEC,
        out_shape=jax.ShapeDtypeStruct((n_chunks, 1, FP_LANES), jnp.int32),
        interpret=interpret,
    )(xc)


def _fused_capture_kernel(x_ref, pfp_ref, fp_ref, cnt_ref, idx_ref, out_ref):
    """One chunk per grid step: hash, compare against the previous
    snapshot's fingerprint, and — when dirty — append the chunk to the
    compaction buffer at the running dirty count. TPU grids execute
    sequentially, so ``cnt_ref`` (an SMEM scalar revisited by every
    step) is a prefix sum over the dirty mask: each chunk lands at its
    final compacted position in the same pass that detected it."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        cnt_ref[0] = 0

    x = x_ref[0]                                     # [R, BLOCK]
    fp = _fp_row(x)
    fp_ref[0] = fp
    dirty = jnp.sum((fp != pfp_ref[0]).astype(jnp.int32)) > 0
    k = cnt_ref[0]
    capacity = idx_ref.shape[0]

    @pl.when(jnp.logical_and(dirty, k < capacity))
    def _():
        idx_ref[k] = i
        out_ref[k] = x

    @pl.when(dirty)
    def _():
        # counted past capacity on purpose: the host reads the final
        # count to detect overflow (fall back to the two-launch path)
        cnt_ref[0] = k + 1


def fused_capture_blocks(xc: jax.Array, prev_fp: jax.Array, capacity: int,
                         *, interpret: bool = False):
    """Single-pass capture: xc [n_chunks, rows_per_chunk, BLOCK] (as for
    ``fingerprint_blocks``), prev_fp i32 [n_chunks, 1,
    FP_LANES] (device-resident) ->

      (fp i32 [n_chunks, 1, FP_LANES],  this snapshot's fingerprints
       count i32 [1],                  total dirty chunks (may exceed
                                       ``capacity`` — overflow signal)
       idx i32 [capacity],             chunk index per compacted slot
       compact [capacity, rows_per_chunk, BLOCK], xc's dtype)

    The leaf is read from HBM exactly once; fingerprint compare and
    dirty compaction happen in the same VMEM pass (vs the two-launch
    path: one fingerprint read + a host round-trip + a gather re-read).
    ``count`` and ``idx`` live in SMEM (scalar state and dynamic
    indices); ``compact`` stays VMEM-resident for the whole grid, so
    ops.py bounds ``capacity * chunk_bytes`` (~8 MB, which fits the
    chip's default scoped VMEM). Only ``count`` rows are meaningful.
    """
    n_chunks, rows, _ = xc.shape
    assert prev_fp.shape == (n_chunks, 1, FP_LANES), (prev_fp.shape, n_chunks)
    assert capacity >= 1
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _fused_capture_kernel,
        grid=(n_chunks,),
        in_specs=[_chunk_spec(rows), _FP_SPEC],
        out_specs=[
            _FP_SPEC, smem, smem,
            pl.BlockSpec((capacity, rows, BLOCK), lambda i: (0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks, 1, FP_LANES), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
            jax.ShapeDtypeStruct((capacity,), jnp.int32),
            jax.ShapeDtypeStruct((capacity, rows, BLOCK), xc.dtype),
        ],
        interpret=interpret,
    )(xc, prev_fp)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_blocks(q: jax.Array, scale: jax.Array, *,
                      interpret: bool = False):
    """q int8 [nb, BLOCK], scale f32 [nb, 1] -> f32 [nb, BLOCK]."""
    nb = q.shape[0]
    rows = min(ROWS_PER_TILE, nb)
    assert nb % rows == 0
    grid = (nb // rows,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, BLOCK), jnp.float32),
        interpret=interpret,
    )(q, scale)

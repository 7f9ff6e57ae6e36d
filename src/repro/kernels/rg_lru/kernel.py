"""Pallas TPU kernel: RG-LRU gated linear recurrence (forward).

The recurrence is elementwise over channels (embarrassingly parallel on
the VPU lanes) and sequential over time. Grid = (batch, channel_blocks,
time_chunks); time is the sequential axis carrying the hidden state
[block_w] in VMEM scratch; within a chunk a fori_loop steps the
recurrence on [block_w]-wide vectors. Channel blocks of 512 lanes keep
x/r/i chunk tiles (3 x Q x 512 x 4B = 1.5 MB at Q=256) VMEM-resident.

This layout means a width-sharded RG-LRU layer (width over the `model`
axis) runs the kernel per shard with zero cross-chip traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_C = 8.0


def _rglru_kernel(x_ref, r_ref, i_ref, lam_ref, y_ref, h_sc, *, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_sc[...] = jnp.zeros_like(h_sc)

    x = x_ref[0].astype(jnp.float32)     # [Q, W]
    r = r_ref[0].astype(jnp.float32)
    gi = i_ref[0].astype(jnp.float32)
    lam = lam_ref[...].astype(jnp.float32)  # [W]

    log_a = -_C * jax.nn.softplus(lam)[None, :] * r      # [Q, W]
    a = jnp.exp(log_a)
    gate = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    b = gate * (gi * x)

    def step(t, carry):
        h, ybuf = carry
        h = a[t] * h + b[t]
        ybuf = jax.lax.dynamic_update_index_in_dim(ybuf, h, t, 0)
        return h, ybuf

    h0 = h_sc[...]
    y0 = jnp.zeros((q, x.shape[1]), jnp.float32)
    h_last, y = jax.lax.fori_loop(0, q, step, (h0, y0))
    y_ref[0] = y.astype(y_ref.dtype)
    h_sc[...] = h_last


def rg_lru_fwd(x, r, i, lam, *, chunk: int = 256, block_w: int = 512,
               interpret: bool = False):
    """x, r, i: [B, S, W]; lam: [W] -> h sequence [B, S, W]."""
    B, S, W = x.shape
    assert S % chunk == 0
    bw = min(block_w, W)
    assert W % bw == 0
    nc = S // chunk
    nw = W // bw

    grid = (B, nw, nc)
    kern = functools.partial(_rglru_kernel, q=chunk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((bw,), lambda b, w, c: (w,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bw), lambda b, w, c: (b, c, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), x.dtype),
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(x, r, i, lam)

"""Pallas TPU kernel: Mamba-2 chunked SSD scan (forward).

Grid = (batch, heads, chunks); the chunk axis is sequential ("arbitrary")
and carries the running inter-chunk state [head_dim, d_state] in VMEM
scratch — the HBM traffic is exactly one read of (x, dt, B, C) and one
write of y per token, with the O(Q^2) intra-chunk work done on the MXU
from VMEM. Chunk length 128-256 balances the quadratic intra term
against state-passing overhead (same blocking as models/ssm.ssd_chunked,
which is the oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_sc, *,
                q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0, 0].astype(jnp.float32)          # [Q, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)        # [Q]
    A = a_ref[0].astype(jnp.float32)             # scalar for this head
    Bm = b_ref[0].astype(jnp.float32)            # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)            # [Q, N]

    dA = dt * A                                   # [Q], negative
    cum = jnp.cumsum(dA)                          # [Q]
    xdt = x * dt[:, None]

    # intra-chunk: scores (C_i . B_j) * exp(cum_i - cum_j), causal
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    seg = cum[:, None] - cum[None, :]
    L = jnp.where(iota_i >= iota_j, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_intra = jax.lax.dot_general(scores * L, xdt,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * C_i . state^T   (state: [hd, N])
    state = state_sc[...]
    y_inter = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y = y_intra + y_inter * jnp.exp(cum)[:, None]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(cum[-1]) + sum_t e^{cum[-1]-cum_t}
    #                         xdt_t (x) B_t
    decay_end = jnp.exp(cum[q - 1] - cum)         # [Q]
    upd = jax.lax.dot_general(xdt * decay_end[:, None], Bm,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_sc[...] = state * jnp.exp(cum[q - 1]) + upd


def ssd_scan_fwd(x, dt, A, Bm, Cm, *, chunk: int = 128,
                 interpret: bool = False):
    """x: [B,S,H,hd]; dt: [B,S,H]; A: [H]; Bm/Cm: [B,S,N] -> y [B,S,H,hd].

    S must be a multiple of `chunk` (ops.py pads)."""
    Bsz, S, H, hd = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    # layout: [B, H, nc, Q, ...] blocks
    xt = x.transpose(0, 2, 1, 3)                  # [B,H,S,hd]
    dtt = dt.transpose(0, 2, 1)                   # [B,H,S]

    grid = (Bsz, H, nc)
    kern = functools.partial(_ssd_kernel, q=chunk)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    y = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hd),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(xt, dtt, A, Bm, Cm)
    return y.transpose(0, 2, 1, 3)

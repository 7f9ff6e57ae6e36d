"""Pallas TPU kernel: blocked causal GQA flash attention (forward).

TPU-native adaptation (DESIGN.md): rather than porting the CUDA warp
layout, blocks are sized for the MXU (128-aligned bq x bk score tiles)
and VMEM residency. Grid = (batch, q_heads, q_blocks, kv_blocks); the kv
axis is the innermost (sequential) dimension, carrying the streaming
softmax state (m, l, acc) in VMEM scratch across kv steps — the same
recurrence models/layers.chunked_attention uses, so that pure-jnp path is
the oracle.

GQA is expressed in the BlockSpec index maps: the kv block index maps
h -> h // group, so no head replication is materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  scale: float, causal: bool, window: int,
                  bq: int, bk: int, n_kv: int, sq: int, skv: int,
                  with_lse: bool = False):
    if with_lse:
        lse_ref, m_sc, l_sc, acc_sc = rest
    else:
        (m_sc, l_sc, acc_sc), lse_ref = rest, None
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < skv
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window > 0:
        valid = valid & (q_pos - k_pos < window)

    # whole-block skip (causal upper triangle / outside window): the
    # scratch state is untouched, so skipped blocks cost ~nothing.
    block_live = jnp.bool_(True)
    if causal:
        block_live = block_live & ((j * bk) <= (i * bq + bq - 1))
    if window > 0:
        block_live = block_live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = m_sc[...] + jnp.log(l)


def flash_attention_fwd(
    q: jax.Array,              # [B, H, Sq, hd]
    k: jax.Array,              # [B, Hkv, Skv, hd]
    v: jax.Array,              # [B, Hkv, Skv, hd]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    return_lse: bool = False,
):
    B, H, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    scale = 1.0 / np.sqrt(hd)

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    # pad seq dims to block multiples (masked via skv/sq bounds)
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    n_q = qp.shape[2] // bq
    n_kv = kp.shape[2] // bk

    grid = (B, H, n_q, n_kv)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=n_kv, sq=Sq, skv=Skv, with_lse=return_lse)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    out_specs = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    out_shape = jax.ShapeDtypeStruct(qp.shape, q.dtype)
    if return_lse:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct(qp.shape[:3], jnp.float32)]
    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(qp, kp, vp)
    if return_lse:
        out, lse = res
        return out[:, :, :Sq], lse[:, :, :Sq]
    return res[:, :, :Sq]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)

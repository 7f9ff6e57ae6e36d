"""Pallas TPU kernels: flash attention backward (dq, dk, dv).

Standard two-kernel split (no atomics on TPU — each kernel owns the
accumulator that matches its grid order):
  * dq kernel:   grid (B, H, i, j) — kv sequential, dq accumulates in
                 VMEM scratch across j (same layout as the forward).
  * dk/dv kernel: grid (B, Hkv, j, i*G) — q-block x group sequential,
                 dk/dv accumulate across (i, g); GQA groups fold into
                 the sequential axis so a kv head sees all its q heads.

Both recompute p from (q, k, softmax stats) per tile — the flash trade:
O(S^2) recompute to keep HBM traffic linear. The forward kernel is
extended to emit the logsumexp row stats (saved residual).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _masks(i, j, bq, bk, sq, skv, causal, window):
    q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = (k_pos < skv) & (q_pos < sq)
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window > 0:
        valid = valid & (q_pos - k_pos < window)
    return valid


# --- dq ---------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_sc, *, scale, causal, window, bq, bk, n_kv, sq, skv):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    live = jnp.bool_(True)
    if causal:
        live = live & ((j * bk) <= (i * bq + bq - 1))
    if window > 0:
        live = live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                       # [bq]
        delta = delta_ref[0, 0]                   # [bq]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = _masks(i, j, bq, bk, sq, skv, causal, window)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        acc_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


# --- dk / dv -----------------------------------------------------------------

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_sc, dv_sc, *,
                scale, causal, window, bq, bk, n_qg, sq, skv, group):
    j, ig = pl.program_id(2), pl.program_id(3)
    i = ig // group   # q block

    @pl.when(ig == 0)
    def _():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    live = jnp.bool_(True)
    if causal:
        live = live & ((j * bk) <= (i * bq + bq - 1))
    if window > 0:
        live = live & ((i * bq) - (j * bk + bk - 1) < window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = _masks(i, j, bq, bk, sq, skv, causal, window)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])             # [bq, bk]
        dv_sc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) / scale

    @pl.when(ig == n_qg - 1)
    def _():
        # ds was computed against the pre-scaled q, so the /scale in the
        # accumulation already restored raw-q units — write through.
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, lse, do, *,
    causal=True, window=0, block_q=128, block_k=128, interpret=False,
):
    """q:[B,H,Sq,hd] k/v:[B,Hkv,Skv,hd] out/do:[B,H,Sq,hd] lse:[B,H,Sq].
    Returns (dq, dk, dv) with dk/dv summed over each kv head's group."""
    B, H, Sq, hd = q.shape
    _, Hkv, Skv, _ = k.shape
    group = H // Hkv
    scale = 1.0 / np.sqrt(hd)
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)

    from repro.kernels.flash_attention.kernel import _pad_to
    qp, dop, outp = (_pad_to(x, 2, bq) for x in (q, do, out))
    kp, vp = (_pad_to(x, 2, bk) for x in (k, v))
    lsep = _pad_to(lse, 2, bq)
    n_q = qp.shape[2] // bq
    n_kv = kp.shape[2] // bk

    # delta = rowsum(do * out)  [B,H,Sq]
    delta = jnp.sum(dop.astype(jnp.float32) * outp.astype(jnp.float32),
                    axis=-1)

    def cp(sem):
        if interpret:
            return {}
        return {"compiler_params":
                pltpu.CompilerParams(dimension_semantics=sem)}

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=n_kv,
                          sq=Sq, skv=Skv),
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        **cp(("parallel", "parallel", "parallel", "arbitrary")),
    )(qp, kp, vp, dop, lsep, delta)

    n_qg = n_q * group
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_qg=n_qg,
                          sq=Sq, skv=Skv, group=group),
        grid=(B, Hkv, n_kv, n_qg),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, hk, j, ig, g=group:
                         (b, hk * g + ig % g, ig // g, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, hk, j, ig: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, hk, j, ig: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, hk, j, ig, g=group:
                         (b, hk * g + ig % g, ig // g, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, hk, j, ig, g=group:
                         (b, hk * g + ig % g, ig // g)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, hk, j, ig, g=group:
                         (b, hk * g + ig % g, ig // g)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, hk, j, ig: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, hk, j, ig: (b, hk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
        **cp(("parallel", "parallel", "parallel", "arbitrary")),
    )(qp, kp, vp, dop, lsep, delta)

    return dq[:, :, :Sq], dk[:, :, :Skv], dv[:, :, :Skv]

"""Mamba-2 blocks: chunked SSD (state-space duality) scan.

Training/prefill uses the chunked SSD algorithm (quadratic within a
Q-token chunk, linear state passing across chunks) — the same blocking the
Pallas kernel (kernels/ssd_scan) implements on TPU. Decode is the O(1)
recurrent update. Head dim / state sizes follow arXiv:2405.21060.

Sharding: heads shard over the ``model`` axis; the (group-shared) B/C
projections and conv params are replicated (tiny).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models.layers import norm_template, apply_norm, rmsnorm

CHUNK = 256


def dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    return d_in, nh, cfg.ssm_state


def ssm_block_template(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    d_in, nh, ds = dims(cfg)
    hd = cfg.ssm_head_dim
    cw = cfg.ssm_conv_width
    return {
        "norm": norm_template(cfg),
        "w_z": P((d, nh, hd), ("embed", "heads", None), fan_in=d),
        "w_x": P((d, nh, hd), ("embed", "heads", None), fan_in=d),
        "w_B": P((d, ds), ("embed", None), fan_in=d),
        "w_C": P((d, ds), ("embed", None), fan_in=d),
        "w_dt": P((d, nh), ("embed", "heads"), fan_in=d),
        "conv_x": P((cw, nh, hd), (None, "heads", None), init="scaled", fan_in=cw),
        "conv_B": P((cw, ds), (None, None), init="scaled", fan_in=cw),
        "conv_C": P((cw, ds), (None, None), init="scaled", fan_in=cw),
        "conv_bx": P((nh, hd), ("heads", None), init="zeros"),
        "conv_bB": P((ds,), (None,), init="zeros"),
        "conv_bC": P((ds,), (None,), init="zeros"),
        "A_log": P((nh,), ("heads",), init="ssm_a", dtype="float32"),
        "D": P((nh,), ("heads",), init="ones", dtype="float32"),
        "dt_bias": P((nh,), ("heads",), init="ssm_dt", dtype="float32"),
        "out_norm": P((nh, hd), ("heads", None), init="zeros", dtype="float32"),
        "w_out": P((nh, hd, d), ("heads", None, "embed"), fan_in=d_in),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv via shifted adds. x: [B,S,...ch]; w: [cw,...ch].

    Returns (y, new_state) where state is the trailing cw-1 inputs."""
    cw = w.shape[0]
    if state is None:
        pad = [(0, 0)] * x.ndim
        pad[1] = (cw - 1, 0)
        xp = jnp.pad(x, pad)
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = sum(xp[:, i:i + S] * w[i] for i in range(cw))
    new_state = xp[:, xp.shape[1] - (cw - 1):]
    return jax.nn.silu(y + b), new_state


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                Bm: jax.Array, Cm: jax.Array,
                chunk: int = CHUNK,
                init_state: Optional[jax.Array] = None):
    """Chunked SSD. x:[B,S,H,P] dt:[B,S,H] A:[H] Bm/Cm:[B,S,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N]). f32 math."""
    Bsz, S, H, Pd = x.shape
    N = Bm.shape[-1]
    q = min(chunk, S)
    assert S % q == 0, (S, q)
    nc = S // q

    # chunk-major layout for the scan: [nc, B, q, ...]
    xf = x.astype(jnp.float32).reshape(Bsz, nc, q, H, Pd).transpose(1, 0, 2, 3, 4)
    dtf = dt.astype(jnp.float32).reshape(Bsz, nc, q, H).transpose(1, 0, 2, 3)
    Bf = Bm.astype(jnp.float32).reshape(Bsz, nc, q, N).transpose(1, 0, 2, 3)
    Cf = Cm.astype(jnp.float32).reshape(Bsz, nc, q, N).transpose(1, 0, 2, 3)
    Af = A.astype(jnp.float32)

    def scan_fn(s_prev, inp):
        # ALL per-chunk work lives inside the scan body — the same
        # blocking as kernels/ssd_scan, so (a) the O(q^2) intra tiles
        # never exist for more than one chunk at a time and (b) the HLO
        # analyzer's innermost-loop kernel adjustment applies (this loop
        # IS the Pallas kernel on TPU).
        xc, dtc, bc, cc = inp                       # [B,q,H,Pd] etc.
        dA = dtc * Af                               # [B,q,H]
        cum = jnp.cumsum(dA, axis=1)
        xdt = xc * dtc[..., None]

        seg = cum.transpose(0, 2, 1)                # [B,H,q]
        diff = seg[..., :, None] - seg[..., None, :]
        ii = jnp.arange(q)
        causal = ii[:, None] >= ii[None, :]
        # mask before exp: above the diagonal diff is a positive sum
        # that overflows exp to inf over a long chunk, and the masked
        # inf would still turn the gradient to 0 * inf = nan
        L = jnp.exp(jnp.where(causal, diff, -jnp.inf))   # [B,H,q,q]
        scores = jnp.einsum("bin,bjn->bij", cc, bc)  # [B,q,q]
        y_intra = jnp.einsum("bhij,bjhp->bihp", scores[:, None] * L, xdt)

        in_decay = jnp.exp(cum)                     # [B,q,H]
        y_inter = jnp.einsum("bqn,bhpn,bqh->bqhp", cc, s_prev, in_decay)

        decay_to_end = jnp.exp(cum[:, -1:, :] - cum)
        st = jnp.einsum("bqn,bqhp,bqh->bhpn", bc, xdt, decay_to_end)
        s_new = s_prev * jnp.exp(cum[:, -1, :])[:, :, None, None] + st
        return s_new, y_intra + y_inter

    s0 = (jnp.zeros((Bsz, H, Pd, N), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    s_final, ys = jax.lax.scan(scan_fn, s0, (xf, dtf, Bf, Cf))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, Pd)
    return y, s_final


def ssd_decode_step(x, dt, A, Bm, Cm, state):
    """One-token recurrence. x:[B,1,H,P] dt:[B,1,H] Bm/Cm:[B,1,N]
    state:[B,H,P,N] -> (y [B,1,H,P], new_state)."""
    xf = x.astype(jnp.float32)[:, 0]
    dtf = dt.astype(jnp.float32)[:, 0]
    Bf = Bm.astype(jnp.float32)[:, 0]
    Cf = Cm.astype(jnp.float32)[:, 0]
    dec = jnp.exp(dtf * A.astype(jnp.float32))       # [B,H]
    upd = jnp.einsum("bhp,bn->bhpn", xf * dtf[..., None], Bf)
    s_new = state.astype(jnp.float32) * dec[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", s_new, Cf)
    return y[:, None], s_new


def ssm_block_forward(
    cfg: ModelConfig,
    p: Dict[str, Any],
    x: jax.Array,                     # [B,S,D]
    cache: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full Mamba-2 block (pre-norm, residual added by caller)."""
    B, S, D = x.shape
    d_in, nh, ds = dims(cfg)
    hd = cfg.ssm_head_dim
    h = apply_norm(cfg, p["norm"], x)

    z = jnp.einsum("bsd,dhp->bshp", h, p["w_z"])
    xs = jnp.einsum("bsd,dhp->bshp", h, p["w_x"])
    Bm = jnp.einsum("bsd,dn->bsn", h, p["w_B"])
    Cm = jnp.einsum("bsd,dn->bsn", h, p["w_C"])
    dt_raw = jnp.einsum("bsd,dh->bsh", h, p["w_dt"])

    cs_x = cache["conv_x"] if cache is not None else None
    cs_B = cache["conv_B"] if cache is not None else None
    cs_C = cache["conv_C"] if cache is not None else None
    xs, ns_x = _causal_conv(xs, p["conv_x"], p["conv_bx"], cs_x)
    Bm, ns_B = _causal_conv(Bm, p["conv_B"], p["conv_bB"], cs_B)
    Cm, ns_C = _causal_conv(Cm, p["conv_C"], p["conv_bC"], cs_C)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    if cache is not None and S == 1:
        y, s_new = ssd_decode_step(xs, dt, A, Bm, Cm, cache["state"])
    else:
        init = cache["state"] if cache is not None else None
        chunk = CHUNK if S % CHUNK == 0 else S
        y, s_new = ssd_chunked(xs, dt, A, Bm, Cm, chunk=chunk, init_state=init)

    y = y + xs.astype(jnp.float32) * p["D"][:, None]
    # gated RMSNorm (per-head scale), then output projection
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.norm_eps) * (1.0 + p["out_norm"])
    out = jnp.einsum("bshp,hpd->bsd", y.astype(x.dtype), p["w_out"])

    new_cache = None
    if cache is not None:
        new_cache = {"conv_x": ns_x, "conv_B": ns_B, "conv_C": ns_C,
                     "state": s_new}
    return out, new_cache


def ssm_cache_spec(cfg: ModelConfig, batch: int):
    """Abstract cache entry for one SSM block."""
    d_in, nh, ds = dims(cfg)
    hd = cfg.ssm_head_dim
    cw = cfg.ssm_conv_width
    f32, bf16 = jnp.float32, jnp.dtype(cfg.dtype)
    return {
        "conv_x": jax.ShapeDtypeStruct((batch, cw - 1, nh, hd), bf16),
        "conv_B": jax.ShapeDtypeStruct((batch, cw - 1, ds), bf16),
        "conv_C": jax.ShapeDtypeStruct((batch, cw - 1, ds), bf16),
        "state": jax.ShapeDtypeStruct((batch, nh, hd, ds), f32),
    }

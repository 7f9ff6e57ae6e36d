"""Mixture-of-Experts FFN with expert parallelism.

Dispatch strategy (TPU-native, see DESIGN.md §5): activations are
replicated across the ``model`` axis (standard TP residual stream), expert
weights are sharded over ``model`` (E/M experts per shard). Each model
shard locally dispatches the tokens routed to *its* experts — a
scatter-add into an [E_local * C, D] capacity buffer, never a [T, E, C]
one-hot — computes its experts, gathers back, and the combine is a psum
over ``model`` (the same all-reduce pattern a dense TP FFN would pay).

This avoids GShard's giant dispatch einsum and needs no all-to-all in the
baseline. An all-to-all + sequence-sharded variant (cuts combine bytes by
the TP degree) is the §Perf hillclimb for collective-bound MoE cells —
see moe_forward(seq_sharded=True).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models import layers as L
from repro.parallel import context as pctx


def moe_template(cfg: ModelConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.resolved_moe_d_ff, cfg.n_experts
    t: Dict[str, Any] = {
        "router": P((d, e), ("embed", None), fan_in=d, dtype="float32"),
        "w_gate": P((e, d, f), ("experts", "embed", "ff"), fan_in=d),
        "w_up": P((e, d, f), ("experts", "embed", "ff"), fan_in=d),
        "w_down": P((e, f, d), ("experts", "ff", "embed"), fan_in=f),
    }
    if cfg.n_shared_experts:
        t["shared"] = L.mlp_template(cfg, cfg.n_shared_experts * f)
    return t


def _capacity(n_tokens: int, k: int, n_experts: int, cf: float) -> int:
    c = int(np.ceil(cf * n_tokens * k / n_experts))
    return max(8, int(np.ceil(c / 8)) * 8)


def _local_expert_ffn(buf: jax.Array, w_gate, w_up, w_down, capacity: int):
    """buf: [E_local * C + 1, D] -> same shape through the local experts."""
    el = w_gate.shape[0]
    xb = buf[:-1].reshape(el, capacity, -1)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xb, w_up)
    yb = jnp.einsum("ecf,efd->ecd", h, w_down)
    y = yb.reshape(el * capacity, -1)
    return jnp.concatenate([y, jnp.zeros_like(buf[-1:])], axis=0)


def _dispatch_compute_combine(
    x: jax.Array,             # [T, D] local tokens
    gates: jax.Array,         # [T, K] f32
    idx: jax.Array,           # [T, K] int32 global expert ids
    w_gate, w_up, w_down,     # local expert weights [El, ...]
    shard_index,              # scalar: which expert shard am I
    n_shards: int,
    capacity: int,
) -> jax.Array:
    """Pure per-shard MoE math. Works for n_shards == 1 (tests) too."""
    t, k = idx.shape
    el = w_gate.shape[0]
    d = x.shape[-1]

    local = idx - shard_index * el                     # [T, K]
    mine = (local >= 0) & (local < el)
    local_c = jnp.where(mine, local, 0)

    # rank of each (token, choice) within its expert, counted jointly over
    # all K choices so capacity is shared. [T*K, El] cumsum — El is per-shard
    # (small), so this stays tiny where a [T, E_global, C] one-hot would not.
    onehot = (local_c.reshape(t * k, 1) == jnp.arange(el)[None, :]) & \
        mine.reshape(t * k, 1)
    ranks = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    my_rank = jnp.sum(jnp.where(onehot, ranks, 0), axis=-1).reshape(t, k)

    ok = mine & (my_rank < capacity)
    overflow = el * capacity                           # drop slot
    slots = jnp.where(ok, local_c * capacity + my_rank, overflow)  # [T, K]

    buf = jnp.zeros((el * capacity + 1, d), x.dtype)
    for j in range(k):                                  # K is small (1 or 8)
        buf = buf.at[slots[:, j]].add(x)                # no token gather

    buf = _local_expert_ffn(buf, w_gate, w_up, w_down, capacity)

    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + buf[slots[:, j]] * gates[:, j].astype(x.dtype)[:, None]
    return out


def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """Router: top-k gates + aux load-balance loss. x: [B,S,D]."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=2), axis=(0, 1))
    prob_frac = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(dispatch_frac * prob_frac)
    return gates, idx.astype(jnp.int32), aux


def moe_forward(
    cfg: ModelConfig,
    p: Dict[str, Any],
    x: jax.Array,                 # [B, S, D]
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B,S,D], aux_loss scalar)."""
    B, S, D = x.shape
    gates, idx, aux = route(cfg, p["router"], x)

    ctx = pctx.current()
    m_size = ctx.model_size() if ctx is not None else 1
    d_size = ctx.data_size() if ctx is not None else 1
    local_tokens = (B // d_size) * S
    cap = _capacity(local_tokens, cfg.experts_per_token, cfg.n_experts,
                    cfg.capacity_factor)

    if ctx is None or (m_size == 1 and d_size == 1):
        y = _dispatch_compute_combine(
            x.reshape(B * S, D), gates.reshape(B * S, -1),
            idx.reshape(B * S, -1), p["w_gate"], p["w_up"], p["w_down"],
            shard_index=0, n_shards=1, capacity=cap)
        y = y.reshape(B, S, D)
    else:
        Pspec = jax.sharding.PartitionSpec
        batch_axes = ctx.batch_spec_axes
        tok_spec = Pspec(batch_axes, None, None)
        gate_spec = Pspec(batch_axes, None, None)
        w_spec = Pspec(ctx.model_axis, None, None)

        def shard_fn(xb, gb, ib, wg, wu, wd):
            m = jax.lax.axis_index(ctx.model_axis) if ctx.model_axis else 0
            bl, sl, _ = xb.shape
            yb = _dispatch_compute_combine(
                xb.reshape(bl * sl, D), gb.reshape(bl * sl, -1),
                ib.reshape(bl * sl, -1), wg, wu, wd,
                shard_index=m, n_shards=m_size, capacity=cap)
            yb = yb.reshape(bl, sl, D)
            if ctx.model_axis:
                yb = jax.lax.psum(yb, ctx.model_axis)
            return yb

        y = jax.shard_map(
            shard_fn, mesh=ctx.mesh,
            in_specs=(tok_spec, gate_spec, gate_spec, w_spec, w_spec, w_spec),
            out_specs=tok_spec, check_vma=False,
        )(x, gates, idx, p["w_gate"], p["w_up"], p["w_down"])

    if cfg.n_shared_experts:
        y = y + L.mlp_forward(cfg, p["shared"], x)
    return y, aux * cfg.router_aux_loss

"""Pipeline parallelism over a mesh axis (GPipe schedule).

Maps the layer-stack dimension onto a mesh axis (on the multi-pod mesh,
the ``pod`` axis: stage boundaries align with the pod boundary, so the
only cross-pod traffic is one activation hand-off per microbatch per
step — the natural placement when inter-pod links are the scarcest).

Implementation: shard_map over the stage axis; each stage owns a
contiguous chunk of stacked layer parameters; a fori_loop runs the
classic (M + S - 1)-tick GPipe schedule with jax.lax.ppermute hand-offs.
Opt-in via ``pipeline_forward`` (the default multi-pod plan folds ``pod``
into data parallelism, which the dry-runs showed is collective-cheaper
for the assigned shapes; PP is the right trade once per-chip batch or
sequence length pushes activation memory past HBM).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(
    block_fn: Callable,        # (layer_params, x) -> x
    stacked_params,            # pytree, leaves [L, ...]
    x_microbatches: jax.Array,  # [M, mb, ...] microbatched inputs
    mesh,
    stage_axis: str = "pod",
    extra_specs: P = P(),
) -> jax.Array:
    """Returns outputs [M, mb, ...] after all L layers, pipelined over
    ``stage_axis``. L must divide by the stage count; M >= stages for
    reasonable bubble fraction (bubble = (S-1)/(M+S-1))."""
    n_stages = int(mesh.shape[stage_axis])
    M = x_microbatches.shape[0]

    def stage_fn(wchunk, xs):
        s = jax.lax.axis_index(stage_axis)

        def run_chunk(x):
            def body(c, wl):
                return block_fn(wl, c), None
            out, _ = jax.lax.scan(body, x, wchunk)
            return out

        def tick(t, carry):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if still in range)
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                 keepdims=False)
            x_in = jnp.where(s == 0, fresh, inflight)
            y = run_chunk(x_in)
            # hand off to the next stage (ring; last stage's send wraps
            # to stage 0 and is ignored)
            y_next = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            # last stage banks its result for microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            bank = (s == n_stages - 1) & (t - (n_stages - 1) >= 0)
            outputs = jnp.where(
                bank,
                jax.lax.dynamic_update_index_in_dim(
                    outputs, y, out_idx, 0),
                outputs)
            return y_next, outputs

        outputs0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(xs[0])
        _, outputs = jax.lax.fori_loop(
            0, M + n_stages - 1, tick, (inflight0, outputs0))
        # broadcast the last stage's outputs to every stage so the
        # result is replicated over the stage axis (loss runs anywhere)
        outputs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            stage_axis)
        return outputs

    in_specs = (
        jax.tree.map(lambda _: P(stage_axis), stacked_params),
        P(*((None,) + tuple(extra_specs))),
    )
    out_specs = P(*((None,) + tuple(extra_specs)))
    return jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )(stacked_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)

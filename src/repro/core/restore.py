"""Restore primitives (paper §II-III): the per-phase building blocks of
the restart sequence.

The lifecycle that *orders* these — materialize the delta chain, fresh
LowerHalf + ``new_incarnation()``, op-log replay, upper-half rebinding
with logical-axes shardings — is owned by ``core.incarnation.
Incarnation``; both the trainer and the serving engine resume through
it. This module keeps the phase primitives it calls (``materialize_
entry``, ``restore_scalar``) plus operator-facing queries over a
checkpoint directory (``restorable_steps``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core.checkpoint import CheckpointManager, RestoredState
from repro.core.split_state import LowerHalf, UpperHalf, fill_like, flatten_with_paths
from repro.parallel.sharding import ParallelPlan, spec_for_axes
from jax.sharding import NamedSharding, PartitionSpec


def restorable_steps(backend) -> List[int]:
    """Committed steps whose full delta chain is still present — a step
    whose base manifest was GC'd (or never landed) is excluded. What an
    operator should consult before picking a restore target.

    Each manifest is read exactly once: one ascending pass memoizes the
    ``base_step`` links, and chain validity propagates base-first (a
    sorted step's base is always <= it, so its verdict is already
    known). O(n) manifest reads, not O(n * chain length)."""
    have = set(backend.list_steps())
    base: Dict[int, Optional[int]] = {}
    for s in sorted(have):
        try:
            base[s] = backend.get_manifest(s).get("base_step")
        except FileNotFoundError:
            continue  # raced a concurrent GC; treat as not restorable
    ok: Dict[int, bool] = {}
    for s in sorted(base):
        b = base[s]
        ok[s] = b is None or ok.get(b, False)
    return [s for s in sorted(have) if ok.get(s, False)]


def fresh_lower_half(restored: RestoredState,
                     mesh_factory: Optional[Callable] = None) -> LowerHalf:
    """Steps 1-2: fresh runtime, replay the log. (Single-phase shim —
    new callers should drive core.incarnation.Incarnation instead.)"""
    lower = LowerHalf(mesh_factory=mesh_factory)
    restored.oplog.replay(lower)
    # the replayed ops become the new incarnation's log (so a subsequent
    # checkpoint of this incarnation carries the full history forward)
    lower.oplog = restored.oplog
    return lower


def materialize_entry(
    restored: RestoredState,
    name: str,
    template,
    plan: Optional[ParallelPlan],
    mesh,
    logical_template=None,
):
    """Step 3 for one entry: path-matched leaves -> sharded device arrays.

    template: abstract pytree (ShapeDtypeStructs or arrays) giving
    structure + dtypes; logical_template: matching pytree of logical axis
    tuples (None leaves -> replicated)."""
    host_tree = fill_like(template, restored.entries[name])

    if mesh is None:
        return jax.tree.map(
            lambda ab, v: jax.numpy.asarray(v, dtype=ab.dtype),
            template, host_tree)

    if logical_template is None:
        shardings = jax.tree.map(
            lambda ab: NamedSharding(mesh, PartitionSpec()), template)
    else:
        # logical leaves are tuples of axis names, which tree.map would
        # recurse into — match by path instead
        lpaths = dict(flatten_with_paths_tuples(logical_template))
        tleaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        shard_leaves = []
        for p, ab in tleaves:
            axes = lpaths.get(jax.tree_util.keystr(p))
            spec = spec_for_axes(plan, axes, ab.shape, mesh) \
                if axes is not None and plan is not None else PartitionSpec()
            shard_leaves.append(NamedSharding(mesh, spec))
        shardings = jax.tree_util.tree_unflatten(treedef, shard_leaves)

    def put(ab, v, sh):
        arr = np.asarray(v)
        if str(arr.dtype) != str(ab.dtype):
            arr = arr.astype(ab.dtype)
        return jax.device_put(arr, sh)

    return jax.tree.map(put, template, host_tree, shardings)


def flatten_with_paths_tuples(tree):
    """Flatten a logical-axes pytree whose leaves are tuples of
    axis-name strings (tuples must not be recursed into)."""
    out = []
    paths = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))[0]
    for p, v in paths:
        out.append((jax.tree_util.keystr(p), v))
    return out


def restore_scalar(restored: RestoredState, name: str):
    """Entries that are plain scalars/int trees (step counters, cursors)."""
    by_path = restored.entries[name]
    if list(by_path) == [""]:
        v = by_path[""]
        return v.item() if hasattr(v, "item") and v.ndim == 0 else v
    return by_path

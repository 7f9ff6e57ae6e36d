"""Mesh construction.

FUNCTIONS, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init;
smoke tests run with the single real CPU device).

Every mesh is built with ``Auto`` axes. ``jax.make_mesh`` defaults to
``Explicit`` axes, under which an op whose output sharding is ambiguous
(the embedding gather on a ``[vocab@model, d@data]`` table) raises
instead of letting the partitioner choose; the models are written for
the partitioner's choice plus ``with_sharding_constraint`` hints.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """A mesh over ``devices`` (default: all of them) with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

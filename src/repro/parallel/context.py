"""Ambient mesh context.

Models are pure functions, but expert-parallel dispatch needs to know the
mesh and axis names to emit shard_map/psum. Rather than threading mesh
handles through every call (which would also poison the upper-half state
with lower-half objects — see core.split_state), the *lower half* installs
a MeshContext for the duration of a step; models read it here.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass
from typing import Optional, Tuple

import jax


@dataclass(frozen=True)
class MeshContext:
    mesh: object                      # jax.sharding.Mesh (or AbstractMesh)
    data_axes: Tuple[str, ...]        # ("data",) or ("pod", "data")
    model_axis: Optional[str]         # "model" (None = no tensor parallelism)

    @property
    def batch_spec_axes(self):
        return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]

    def model_size(self) -> int:
        if self.model_axis is None:
            return 1
        return int(self.mesh.shape[self.model_axis])

    def data_size(self) -> int:
        n = 1
        for a in self.data_axes:
            n *= int(self.mesh.shape[a])
        return n


_ctx: contextvars.ContextVar[Optional[MeshContext]] = contextvars.ContextVar(
    "repro_mesh_context", default=None)


def current() -> Optional[MeshContext]:
    return _ctx.get()


@contextlib.contextmanager
def mesh_context(mesh, data_axes=("data",), model_axis="model"):
    tok = _ctx.set(MeshContext(mesh, tuple(data_axes), model_axis))
    try:
        yield _ctx.get()
    finally:
        _ctx.reset(tok)


def single_device_context():
    """Context for tests/examples on one device: a 1x1 mesh."""
    dev = jax.devices()[0]
    mesh = jax.sharding.Mesh([[dev]], ("data", "model"))
    return mesh_context(mesh)

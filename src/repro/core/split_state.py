"""Split-process state partition (paper §II), adapted to a JAX runtime.

``UpperHalf`` — the application half: semantic training/serving state
(params, optimizer moments, RNG counters, data cursor, step). Stored as
*logically addressed* pytrees: every leaf is reachable by a stable path
string and annotated with logical sharding axes. Nothing here references
a device, a mesh, or a compiled object; this is the only state a
checkpoint saves.

``LowerHalf`` — the driver half: mesh bound to physical devices, compiled
executables, live cache allocations, schedule overrides, data-shard
assignment. Never serialized. Every mutating entry point both executes
and appends to the op-log, so a fresh LowerHalf can be driven back into an
equivalent state by replay (core.oplog).
"""
from __future__ import annotations

import json
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.api.errors import RestoreError
from repro.core.oplog import (
    OpLog, Op, MeshCreate, Compile, CacheAlloc, CacheFree, DataAdvance,
    DataReassign, ScheduleSet,
)
from repro.core.virtual_ids import HandleTable, DeviceMap, VirtualId
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# upper half
# ---------------------------------------------------------------------------

def flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(jax.tree_util.keystr(p), v) for p, v in leaves]


# keystr renders dict keys with repr(), which picks double quotes when
# the key itself contains a single quote — accept both forms
_DICT_KEY = re.compile(
    r"\[(?:'((?:[^'\\]|\\.)*)'|\"((?:[^\"\\]|\\.)*)\")\]")


def tree_from_paths(by_path: Dict[str, Any]) -> Any:
    """Rebuild a nested dict from keystr paths, no template required.

    Inverse of ``flatten_with_paths`` for dict-only pytrees (paths like
    ``['queue']['0']['prompt']``). State whose *structure* is data — the
    serving scheduler's request queue, whose shape differs checkpoint to
    checkpoint — restores through this instead of ``fill_like``. The
    path "" (a bare leaf) returns the leaf itself."""
    if list(by_path) == [""]:
        return by_path[""]
    out: Dict[str, Any] = {}
    for path, leaf in by_path.items():
        keys = []
        pos = 0
        for m in _DICT_KEY.finditer(path):
            if m.start() != pos:
                break
            k = m.group(1) if m.group(1) is not None else m.group(2)
            keys.append(k.replace("\\'", "'").replace('\\"', '"')
                         .replace("\\\\", "\\"))
            pos = m.end()
        if pos != len(path) or not keys:
            raise RestoreError(f"non-dict path {path!r}; use fill_like with "
                               "a structural template instead")
        node = out
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = leaf
    return out


def fill_like(template, by_path: Dict[str, Any]):
    """Rebuild a pytree with `template`'s structure from path->leaf map."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for p, tleaf in paths:
        key = jax.tree_util.keystr(p)
        if key not in by_path:
            raise KeyError(f"checkpoint missing leaf {key}")
        leaves.append(by_path[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclass
class StateEntry:
    kind: str                  # params | opt_state | rng | data_cursor | ...
    tree: Any                  # pytree (device or host arrays / scalars)
    logical: Any = None        # matching pytree of logical axis tuples


class UpperHalf:
    """Named registry of semantic state entries."""

    def __init__(self) -> None:
        self._entries: Dict[str, StateEntry] = {}

    def register(self, name: str, kind: str, tree, logical=None) -> None:
        self._entries[name] = StateEntry(kind, tree, logical)

    def update(self, name: str, tree) -> None:
        self._entries[name].tree = tree

    def get(self, name: str):
        return self._entries[name].tree

    def entry(self, name: str) -> StateEntry:
        return self._entries[name]

    def names(self) -> List[str]:
        return list(self._entries)

    def items(self):
        return self._entries.items()

    def to_host(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Pull every tensor off device: {entry: {leaf_path: np.ndarray}}.

        This is the checkpoint's payload — note it contains no handles,
        no devices, no executables (the split-process guarantee).

        np.array (not asarray): host-resident numpy leaves must be
        COPIED at the snapshot point, or a caller mutating them after
        save() returns would race the async background writer."""
        out: Dict[str, Dict[str, np.ndarray]] = {}
        for name, e in self._entries.items():
            out[name] = {
                p: np.array(jax.device_get(v))
                for p, v in flatten_with_paths(e.tree)
            }
        return out

    def structure(self) -> Dict[str, Any]:
        """JSON-able description (kinds + leaf shapes/dtypes + logical)."""
        desc = {}
        for name, e in self._entries.items():
            leaves = {}
            for p, v in flatten_with_paths(e.tree):
                # shape/dtype description needs no device transfer:
                # array-likes carry both already; scalar/non-array
                # leaves (int, float, list) are viewed through numpy
                arr = v if hasattr(v, "shape") else np.asarray(v)
                leaves[p] = {"shape": list(arr.shape),
                             "dtype": str(arr.dtype)}
            logical = None
            if e.logical is not None:
                logical = {p: list(ax) for p, ax in flatten_with_paths(e.logical)}
            desc[name] = {"kind": e.kind, "leaves": leaves, "logical": logical}
        return desc


# ---------------------------------------------------------------------------
# function registry: Compile ops resolve through here
# ---------------------------------------------------------------------------

# fn_name -> builder(arch, shape_key, plan_key, lower_half) -> callable
FUNCTION_REGISTRY: Dict[str, Callable] = {}


def register_step_fn(name: str):
    def deco(builder):
        FUNCTION_REGISTRY[name] = builder
        return builder
    return deco


# ---------------------------------------------------------------------------
# lower half
# ---------------------------------------------------------------------------

class LowerHalf:
    """The reinitializable driver half.

    Construction is cheap and touches no devices; ``mesh_create`` (direct
    or via replay) binds hardware. A restart constructs a new LowerHalf
    (or calls ``reset()``) and replays the op-log.
    """

    def __init__(self, handles: Optional[HandleTable] = None,
                 oplog: Optional[OpLog] = None,
                 mesh_factory: Optional[Callable] = None) -> None:
        self.handles = handles or HandleTable()
        self.oplog = oplog or OpLog()
        self.devices = DeviceMap()
        # mesh_factory overrides logged mesh geometry (elastic restore)
        self.mesh_factory = mesh_factory
        self.vmesh: Optional[VirtualId] = None
        self.schedule_overrides: Dict[str, float] = {}
        self.data_cursor_replayed = 0
        self.data_assignment: Optional[Tuple[Tuple[int, int], ...]] = None
        self._compiled: Dict[Tuple[str, str, str, str], VirtualId] = {}
        self._lock = threading.RLock()

    # --- logged public API (execute + append) --------------------------

    def mesh_create(self, shape, axes) -> VirtualId:
        with self._lock:
            vmesh = self.handles.allocate("mesh")
            op = self.oplog.append(MeshCreate, vmesh=vmesh,
                                   shape=tuple(shape), axes=tuple(axes))
            self._apply(op)
            return vmesh

    def compile_step(self, fn_name: str, arch: str, shape_key: str,
                     plan_key: str = "") -> VirtualId:
        with self._lock:
            vexec = self.handles.allocate("exec")
            op = self.oplog.append(Compile, vexec=vexec, fn_name=fn_name,
                                   arch=arch, shape_key=shape_key,
                                   plan_key=plan_key)
            self._apply(op)
            return vexec

    def cache_alloc(self, arch: str, batch: int, max_seq: int) -> VirtualId:
        with self._lock:
            vcache = self.handles.allocate("cache")
            op = self.oplog.append(CacheAlloc, vcache=vcache, arch=arch,
                                   batch=batch, max_seq=max_seq)
            self._apply(op)
            return vcache

    def cache_free(self, vcache: VirtualId) -> None:
        with self._lock:
            op = self.oplog.append(CacheFree, vcache=vcache)
            self._apply(op)

    def data_advance(self, n: int) -> None:
        with self._lock:
            op = self.oplog.append(DataAdvance, n=n)
            self._apply(op)

    def data_reassign(self, assignment) -> None:
        with self._lock:
            op = self.oplog.append(
                DataReassign, assignment=tuple(map(tuple, assignment)))
            self._apply(op)

    def schedule_set(self, key: str, value: float) -> None:
        with self._lock:
            op = self.oplog.append(ScheduleSet, key=key, value=float(value))
            self._apply(op)

    # --- resolution ------------------------------------------------------

    @property
    def mesh(self):
        return self.devices.mesh

    def executable(self, vexec: VirtualId):
        return self.handles.translate(vexec)

    def cache(self, vcache: VirtualId):
        return self.handles.translate(vcache)

    # --- replay side -----------------------------------------------------

    def reset(self) -> None:
        """Fresh incarnation: drop all real bindings (the 'kill the driver'
        moment). vids stay allocated; replay rebinds them."""
        self.handles.new_incarnation()
        self.devices = DeviceMap()
        self.vmesh = None
        self.schedule_overrides = {}
        self.data_cursor_replayed = 0
        self.data_assignment = None
        self._compiled = {}

    def apply_op(self, op: Op) -> None:
        """Execute one op without logging (replay path)."""
        self._apply(op)

    def _apply(self, op: Op) -> None:
        if isinstance(op, MeshCreate):
            if self.mesh_factory is not None:
                mesh = self.mesh_factory()
            else:
                mesh = make_mesh(op.shape, op.axes)
            self.devices.bind_mesh(mesh)
            self.handles.bind(op.vmesh, mesh)
            self.vmesh = op.vmesh
        elif isinstance(op, Compile):
            key = (op.fn_name, op.arch, op.shape_key, op.plan_key)
            if key in self._compiled and self.handles.is_bound(
                    self._compiled[key]):
                # identical compilation already live: alias the vid to the
                # existing executable instead of recompiling
                fn = self.handles.translate(self._compiled[key])
            else:
                builder = FUNCTION_REGISTRY[op.fn_name]
                fn = builder(op.arch, op.shape_key, op.plan_key, self)
                self._compiled[key] = op.vexec
            self.handles.bind(op.vexec, fn)
        elif isinstance(op, CacheAlloc):
            from repro.serving.kv_cache import allocate_cache
            cache = allocate_cache(op.arch, op.batch, op.max_seq, self)
            self.handles.bind(op.vcache, cache)
        elif isinstance(op, CacheFree):
            self.handles.release(op.vcache)
        elif isinstance(op, DataAdvance):
            self.data_cursor_replayed += op.n
        elif isinstance(op, DataReassign):
            self.data_assignment = op.assignment
        elif isinstance(op, ScheduleSet):
            self.schedule_overrides[op.key] = op.value
        else:
            raise TypeError(f"unknown op {op}")

    # --- observability (for tests / prune equivalence) -------------------

    def fingerprint(self) -> Dict[str, Any]:
        mesh_shape = None
        try:
            mesh_shape = dict(self.devices.mesh.shape)
        except Exception:
            pass
        compiled = sorted(
            key for key, vexec in self._compiled.items()
            if self.handles.is_bound(vexec))
        live_caches = sorted(
            v.uid for v in self.handles.live_vids() if v.kind == "cache")
        return {
            "mesh": mesh_shape,
            "compiled": compiled,
            "caches": live_caches,
            "schedule": dict(self.schedule_overrides),
            "data_cursor": self.data_cursor_replayed,
            "assignment": self.data_assignment,
        }

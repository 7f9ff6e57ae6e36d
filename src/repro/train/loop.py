"""Trainer: the end-to-end integration of the paper's split-state C/R
with the training substrate.

Normal operation:  every runtime-mutating call (mesh, compile, data
advance, schedule touch) goes through the logged LowerHalf API; semantic
state lives in the UpperHalf; CheckpointManager snapshots the upper half
in the background.

Crash:             the process (or pod) dies. Nothing to do.

Restore:           Trainer.restore() = fresh LowerHalf + op-log replay
(recompiles the step executable, reapplies schedule/data ops) + upper
half rematerialized onto the (possibly different!) mesh. Continuation is
bitwise-identical to the uninterrupted run — tested.
"""
from __future__ import annotations

import json
import warnings
from dataclasses import asdict, dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.api import register_app_kind
from repro.api.app import RestoreContext
from repro.api.session import CheckpointSession
from repro.configs import registry as cfg_registry
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import CheckpointManager, LowerHalf, OpLog, UpperHalf
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import model as M
from repro.optim import (AdamWConfig, ScheduleConfig, abstract_opt_state,
                         init_opt_state, opt_logical_specs)
from repro.parallel.planner import make_plan
from repro.train import step as step_lib


@dataclass
class TrainJob:
    arch: str                  # registry id, or "<id>-smoke"
    shape_key: str
    init_seed: int = 0
    data_seed: int = 1234
    plan_overrides: Optional[Dict[str, Any]] = None

    @property
    def plan_key(self) -> str:
        return json.dumps(self.plan_overrides) if self.plan_overrides else ""


def _resolve_cfg(arch: str) -> ModelConfig:
    return cfg_registry.resolve_config(arch)


class Trainer:
    def __init__(self, job: TrainJob, mesh_shape, mesh_axes,
                 manager: Optional[CheckpointManager] = None,
                 _restored=None):
        self.job = job
        self.cfg = _resolve_cfg(job.arch)
        self.shape = cfg_registry.get_shape(job.shape_key)
        self.manager = manager

        if _restored is None:
            self.lower = LowerHalf()
            self.lower.mesh_create(mesh_shape, mesh_axes)
            self.vexec = self.lower.compile_step(
                "train_step", job.arch, job.shape_key, job.plan_key)
        else:
            self.lower, self.vexec = _restored

        mesh = self.lower.mesh
        self.plan = make_plan(self.cfg, self.shape, mesh)
        if job.plan_overrides:
            self.plan = self.plan.with_(**job.plan_overrides)
        self.opt_cfg = AdamWConfig(quantize_moments=self.cfg.n_params() > 5e10)
        self.pshard, self.oshard = step_lib.train_state_shardings(
            self.cfg, self.plan, mesh, self.opt_cfg)

        # n_shards is a DATA-layout constant (one shard per batch row),
        # never a topology property: batches must be bit-identical across
        # mesh shapes or elastic restore would silently change the data
        # stream (caught by tests/test_elastic_multidev.py).
        dcfg = DataConfig(
            seed=job.data_seed, vocab_size=self.cfg.vocab_size,
            seq_len=self.shape.seq_len, global_batch=self.shape.global_batch,
            n_shards=self.shape.global_batch,
            frames=self.cfg.encoder_seq if self.cfg.is_encoder_decoder else 0,
            frame_dim=self.cfg.frontend_dim)
        self.pipeline = TokenPipeline(dcfg)
        if self.lower.data_assignment:
            self.pipeline.reassign(self.lower.data_assignment)

        self.upper = UpperHalf()
        self._binputs = step_lib.train_input_specs(self.cfg, self.shape)
        self._bshard = step_lib.batch_shardings(self.plan, mesh, self._binputs)

    # --- state construction -------------------------------------------------

    def init_state(self) -> None:
        """Fresh start: initialize params/opt on-mesh and register the
        upper half."""
        rng = jax.random.PRNGKey(self.job.init_seed)
        init = jax.jit(lambda r: M.init_params(self.cfg, r),
                       out_shardings=self.pshard)
        params = init(rng)
        opt_state = jax.jit(
            lambda p: init_opt_state(p, self.opt_cfg),
            out_shardings=self.oshard)(params)
        logical = M.logical_specs(self.cfg)
        self.upper.register("params", "params", params, logical)
        self.upper.register("opt_state", "opt_state", opt_state,
                            opt_logical_specs(logical, self.opt_cfg))
        self.upper.register("step", "step", np.int64(0))
        self.upper.register("data_cursor", "data_cursor", np.int64(0))
        self.upper.register("rng_seed", "rng",
                            np.int64(self.job.init_seed))

    # --- stepping ---------------------------------------------------------

    def _device_batch(self, batch_np):
        return {k: jax.device_put(v, self._bshard[k])
                for k, v in batch_np.items()}

    def train_steps(self, n: int) -> Dict[str, float]:
        fn = self.lower.executable(self.vexec)
        params = self.upper.get("params")
        opt_state = self.upper.get("opt_state")
        step = int(self.upper.get("step"))
        cursor = int(self.upper.get("data_cursor"))
        lr_scale = jnp.float32(
            self.lower.schedule_overrides.get("lr_scale", 1.0))
        metrics = {}
        for _ in range(n):
            batch = self._device_batch(self.pipeline.batch_at(cursor))
            params, opt_state, metrics = fn(
                params, opt_state, batch, jnp.int32(step), lr_scale)
            step += 1
            cursor += 1
            self.lower.data_advance(1)
        self.upper.update("params", params)
        self.upper.update("opt_state", opt_state)
        self.upper.update("step", np.int64(step))
        self.upper.update("data_cursor", np.int64(cursor))
        return {k: float(np.asarray(jax.device_get(v)))
                for k, v in metrics.items()}

    # --- CheckpointableApp protocol (repro.api) -----------------------------

    def checkpoint_state(self) -> UpperHalf:
        return self.upper

    def checkpoint_step(self) -> int:
        return int(self.upper.get("step"))

    def runtime_log(self) -> OpLog:
        return self.lower.oplog

    def job_meta(self) -> Dict[str, Any]:
        return {"kind": "train",
                "arch": self.job.arch, "shape_key": self.job.shape_key,
                "plan_key": self.job.plan_key,
                "init_seed": self.job.init_seed,
                "data_seed": self.job.data_seed}

    def bind(self, restore: RestoreContext) -> None:
        """CheckpointableApp.bind: rematerialize the upper half onto
        this incarnation's (possibly different) mesh. Expects the
        context's lower half already replayed — the "train" binder
        orders the phases."""
        inc = restore.incarnation()
        ab_params = M.init_abstract(self.cfg)
        logical = M.logical_specs(self.cfg)
        params = inc.bind("params", ab_params, plan=self.plan,
                          logical=logical)
        ab_opt = abstract_opt_state(ab_params, self.opt_cfg)
        olog = opt_logical_specs(logical, self.opt_cfg)
        opt_state = inc.bind("opt_state", ab_opt, plan=self.plan,
                             logical=olog)
        self.upper.register("params", "params", params, logical)
        self.upper.register("opt_state", "opt_state", opt_state, olog)
        self.upper.register("step", "step", np.int64(inc.scalar("step")))
        self.upper.register("data_cursor", "data_cursor",
                            np.int64(inc.scalar("data_cursor")))
        self.upper.register("rng_seed", "rng",
                            np.int64(inc.scalar("rng_seed")))
        inc.release()   # host payload rebound on device; don't hold the
        self.incarnation = inc  # checkpoint's RAM for the life of the run

    # --- checkpoint / restore ------------------------------------------------

    def save(self, block: bool = True) -> None:
        assert self.manager is not None
        self.manager.save(self.checkpoint_step(), self.checkpoint_state(),
                          self.runtime_log(), block=block,
                          job_meta=self.job_meta())

    def snapshot(self):
        """Non-blocking checkpoint at the current step boundary: pays
        only the device→staging capture; delta encode + backend writes
        overlap the next train_steps() on the pipeline threads. Returns
        the SnapshotHandle (None if dropped under "skip" backpressure).

        Same payload a ``CheckpointSession`` wrapping this trainer would
        take — the protocol methods are the single source; the trainer
        deliberately does NOT hold a session of its own (one session
        owns an app's lifecycle, and that session is the caller's)."""
        assert self.manager is not None
        return self.manager.save(self.checkpoint_step(),
                                 self.checkpoint_state(),
                                 self.runtime_log(), block=False,
                                 job_meta=self.job_meta())

    def apply_reassignment(self, assignment) -> None:
        """Move data-shard ownership between hosts, as one *logged*
        operation: the DataReassign goes through the lower half (so a
        later restart replays it — the supervisor's hot-spare and
        straggler rebalances survive crashes) and the live pipeline
        adopts it immediately. Batch contents are unchanged — shard
        layout is a data constant, ownership is topology — so training
        stays token-identical across any reassignment."""
        self.lower.data_reassign(assignment)
        self.pipeline.reassign(list(map(tuple, assignment)))

    def train(self, n_steps: int, snapshot_every: Optional[int] = None,
              ) -> Dict[str, float]:
        """Step loop with overlapped checkpointing: snapshots are
        captured at step boundaries and drain in the background."""
        metrics: Dict[str, float] = {}
        for i in range(1, n_steps + 1):
            metrics = self.train_steps(1)
            if snapshot_every and self.manager is not None \
                    and i % snapshot_every == 0:
                self.snapshot()
        if self.manager is not None and snapshot_every:
            self.manager.wait()
        return metrics

    @classmethod
    def restore(cls, manager: CheckpointManager,
                mesh_factory: Optional[Callable] = None,
                step: Optional[int] = None,
                decode_workers: Optional[int] = None,
                rewrite_op: Optional[Callable] = None) -> "Trainer":
        """Legacy shim: delegates to the public session API
        (``repro.api.CheckpointSession.restore``), which resolves the
        "train" binder below through the app-kind registry. Phase
        timings land on ``trainer.incarnation.timings``; ``rewrite_op``
        transforms logged ops before replay (elastic re-shard)."""
        warnings.warn(
            "Trainer.restore is a legacy shim; use "
            "repro.api.CheckpointSession.restore", DeprecationWarning,
            stacklevel=2)
        return CheckpointSession.from_manager(manager).restore(
            step=step, expect_kind="train", mesh_factory=mesh_factory,
            rewrite_op=rewrite_op, workers=decode_workers)

    # --- observability ---------------------------------------------------------

    def params_digest(self) -> str:
        import hashlib
        h = hashlib.blake2b(digest_size=16)
        for path, arr in sorted(
                (p, v) for p, v in
                _flatten(self.upper.get("params"))):
            h.update(path.encode())
            h.update(np.ascontiguousarray(
                np.asarray(jax.device_get(arr))).tobytes())
        return h.hexdigest()


def _flatten(tree):
    from repro.core.split_state import flatten_with_paths
    return flatten_with_paths(tree)


@register_app_kind("train")
def _restore_trainer(restore: RestoreContext) -> Trainer:
    """The "train" restore binder: the Incarnation lifecycle, trainer
    flavor — materialize the delta chain (parallel leaf decode), fresh
    lower half + op-log replay (recompile, reapply runtime ops), then
    ``Trainer.bind`` rematerializes the upper half on the (new) mesh."""
    inc = restore.incarnation()
    inc.materialize()
    jm = restore.job
    job = TrainJob(arch=jm["arch"], shape_key=jm["shape_key"],
                   init_seed=jm.get("init_seed", 0),
                   data_seed=jm.get("data_seed", 1234),
                   plan_overrides=json.loads(jm["plan_key"])
                   if jm.get("plan_key") else None)

    # 1-2: fresh lower half + replay (recompile, reapply runtime ops)
    lower = inc.build_lower()
    vexec = inc.last_compile("train_step")
    assert vexec is not None, "no train_step Compile in the log"

    t = Trainer(job, None, None, manager=restore.manager,
                _restored=(lower, vexec))
    t.bind(restore)   # 3: upper half onto the (new) mesh
    return t

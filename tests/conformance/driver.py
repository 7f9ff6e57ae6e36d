"""The conformance scenario driver: one torture sequence, any app kind.

Every matrix cell runs through the functions here, and this module
imports ONLY ``repro.api`` (enforced by ``test_import_scan``): if a
failure mode needs anything beyond the public session surface, that is
a hole in the API, not a gap for a test helper to paper over. The app
side of each family (how to build a trainer / serving engine / RL
learner, how to advance it, how to hash its semantic state) arrives as
a ``FamilySpec`` of plain callables from ``families.py``.

Failure modes:

  kill     snapshot cadence → drop the app object → restore latest →
           continue → bit-identical to the uninterrupted run
  reslot   elastic restore onto a different topology (serving slots,
           RL actor pool) with work in flight → identical outputs
  shrink   supervisor detects a silent host, decides SHRINK, restores
           onto the survivors → continuation bit-identical
  commit   a crash *between blob writes and the manifest rename* is
           simulated byte-for-byte; reopen → the torn step is invisible,
           the previous step restores, the store still accepts commits
  swap     the same kill sequence under the other checkpoint package —
           the swap is one spec string; state digests agree across
           packages and with the reference
"""
from __future__ import annotations

import glob
import hashlib
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.api import (CheckpointSession, Policy, UpperHalf,
                       parse_store_spec, register_app_kind)

# --- family contract --------------------------------------------------------


@dataclass(frozen=True)
class TrainDrive:
    """How to run a family's stateful workload (drives kill / shrink /
    commit / swap). ``advance`` must be deterministic given the app's
    state alone; ``digest`` must hash every semantic entry."""
    fresh: Callable[[], Any]
    advance: Callable[[Any, int], None]
    digest: Callable[[Any], str]
    step_of: Callable[[Any], int]
    total: int = 6
    interval: int = 2
    restore_kwargs: Callable[[], Dict[str, Any]] = dict


@dataclass(frozen=True)
class ElasticDrive:
    """How to run the family's elastic re-slot scenario: warm leaves
    work in flight, restore re-slots onto a different topology, and
    ``outcome`` must match the uninterrupted ``reference``."""
    fresh: Callable[[], Any]
    warm: Callable[[CheckpointSession, Any], None]
    outcome: Callable[[Any], Any]
    reference: Callable[[], Any]
    reslot_kwargs: Callable[[], Dict[str, Any]]


@dataclass(frozen=True)
class ShrinkDrive:
    """Supervisor world for the shrink scenario."""
    hosts: Tuple[int, ...] = (0, 1, 2)
    dead: int = 0
    n_shards: Optional[int] = None
    restore_kwargs: Any = None          # dict | callable(target) -> dict
    prepare: Optional[Callable[[Any], None]] = None
    check: Optional[Callable[[Any, Any], None]] = None


@dataclass(frozen=True)
class FamilySpec:
    family: str
    train: TrainDrive
    elastic: ElasticDrive
    shrink: ShrinkDrive


# --- reference / cross-package digest caches --------------------------------

_REF: Dict[str, str] = {}
_KILL: Dict[Tuple[str, str], str] = {}


def reference_digest(spec: FamilySpec) -> str:
    """The uninterrupted run's digest, computed once per family (the
    expensive part of every cell; identical across modes by design)."""
    d = _REF.get(spec.family)
    if d is None:
        app = spec.train.fresh()
        spec.train.advance(app, spec.train.total)
        d = spec.train.digest(app)
        _REF[spec.family] = d
    return d


# --- failure modes ----------------------------------------------------------

def run_kill(spec: FamilySpec, store: str) -> str:
    """snapshot → hard kill → restore → continue → bit-identical."""
    dr = spec.train
    want = reference_digest(spec)
    policy = Policy(interval=dr.interval, chain=3, keep_last=4)
    with CheckpointSession(store, policy) as sess:
        app = sess.attach(dr.fresh())
        half = dr.total // 2
        for _ in range(half):
            dr.advance(app, 1)
            sess.maybe_snapshot()
        sess.wait()
        boundary = (half // dr.interval) * dr.interval
        assert 0 < boundary < half, \
            f"{spec.family}: the kill must lose real progress " \
            f"(boundary {boundary}, died at {half})"
        del app                                   # hard kill
        app2 = sess.restore("latest", **dr.restore_kwargs())
        at = dr.step_of(app2)
        assert at == boundary, \
            f"{spec.family}: restored at step {at}, wanted {boundary}"
        dr.advance(app2, dr.total - at)
        got = dr.digest(app2)
    assert got == want, \
        f"{spec.family}: post-restore digest {got} != reference {want}"
    _KILL[(spec.family, store.split(":", 1)[0])] = got
    return got


def run_degraded(spec: FamilySpec, store: str) -> str:
    """One dead peer per shard ring: the replicated package loses a
    host wholesale after the checkpoint commits. The restore must read
    each blob from its surviving copy — same digest as the reference
    run (fallback is a routing decision, never a correctness
    relaxation)."""
    dr = spec.train
    want = reference_digest(spec)
    policy = Policy(interval=dr.interval, chain=3, keep_last=4)
    with CheckpointSession(store, policy) as sess:
        app = sess.attach(dr.fresh())
        half = dr.total // 2
        for _ in range(half):
            dr.advance(app, 1)
            sess.maybe_snapshot()
        sess.wait()
        boundary = (half // dr.interval) * dr.interval
        del app                               # hard kill
        sess.backend.fail_host(1)             # ... and a dead peer

        app2 = sess.restore("latest", **dr.restore_kwargs())
        at = dr.step_of(app2)
        assert at == boundary, \
            f"{spec.family}: degraded restore at {at}, wanted {boundary}"
        dr.advance(app2, dr.total - at)
        got = dr.digest(app2)
    assert got == want, \
        f"{spec.family}: degraded digest {got} != reference {want}"
    return got


def run_reslot(spec: FamilySpec, store: str) -> None:
    """Elastic restore onto a different topology with work in flight."""
    el = spec.elastic
    want = el.reference()
    with CheckpointSession(store, Policy(async_save=False)) as sess:
        app = sess.attach(el.fresh())
        el.warm(sess, app)
        sess.snapshot(block=True)
        del app                                   # hard kill mid-flight
        app2 = sess.restore("latest", **el.reslot_kwargs())
        got = el.outcome(app2)
    assert got == want, \
        f"{spec.family}: re-slotted outcome diverged\n got={got}\nwant={want}"


def run_shrink(spec: FamilySpec, store: str) -> None:
    """Detect a silent host, decide SHRINK, restore onto survivors."""
    dr, sh = spec.train, spec.shrink
    want = reference_digest(spec)
    with CheckpointSession(store, Policy(async_save=False)) as sess:
        app = sess.attach(dr.fresh())
        if sh.prepare is not None:
            sh.prepare(app)
        half = dr.total // 2
        dr.advance(app, half)
        sess.snapshot(block=True)

        clock = [0.0]
        sup = sess.supervise(list(sh.hosts), heartbeat_timeout=3.0,
                             clock=lambda: clock[0], n_shards=sh.n_shards,
                             restore_kwargs=sh.restore_kwargs)

        def tick(alive: List[int]) -> None:
            clock[0] += 1.0
            for h in alive:
                sup.beat(h, half)

        tick(list(sh.hosts))
        tick(list(sh.hosts))
        assert sup.poll() is None, "healthy world produced a decision"

        survivors = [h for h in sh.hosts if h != sh.dead]
        target = None
        for _ in range(8):
            tick(survivors)
            target = sup.poll()
            if target is not None:
                break
        assert target is not None, \
            f"{spec.family}: silent host {sh.dead} never detected"
        assert target.action.name == "SHRINK", \
            f"{spec.family}: decided {target.action.name}, wanted SHRINK"
        assert sorted(target.hosts) == sorted(survivors)

        app2 = sess.app
        assert app2 is not app, "shrink must rebuild the runner"
        at = dr.step_of(app2)
        assert at == half, \
            f"{spec.family}: shrink restored at {at}, wanted {half}"
        if sh.check is not None:
            sh.check(app2, target)
        dr.advance(app2, dr.total - at)
        got = dr.digest(app2)
    assert got == want, \
        f"{spec.family}: post-shrink digest {got} != reference {want}"


def run_churn_grow(spec: FamilySpec, store: str) -> None:
    """The churn round trip, cell-sized: a silent host SHRINKs the
    world, real progress lands on the survivors, the host comes back
    and GROW re-admits it — post-grow continuation bit-identical to the
    uninterrupted reference. Shrink and grow are the same restore
    primitive pointed in opposite directions, and this cell pins that
    the direction flip loses nothing."""
    dr, sh = spec.train, spec.shrink
    want = reference_digest(spec)
    with CheckpointSession(store, Policy(async_save=False)) as sess:
        app = sess.attach(dr.fresh())
        if sh.prepare is not None:
            sh.prepare(app)
        half = dr.total // 2
        dr.advance(app, half)
        sess.snapshot(block=True)

        clock = [0.0]
        sup = sess.supervise(list(sh.hosts), heartbeat_timeout=3.0,
                             clock=lambda: clock[0], n_shards=sh.n_shards,
                             restore_kwargs=sh.restore_kwargs)

        def tick(alive: List[int]) -> None:
            clock[0] += 1.0
            for h in alive:
                sup.beat(h, half)

        survivors = [h for h in sh.hosts if h != sh.dead]
        target = None
        for _ in range(8):
            tick(survivors)
            target = sup.poll()
            if target is not None:
                break
        assert target is not None and target.action.name == "SHRINK", \
            f"{spec.family}: wanted SHRINK, got {target}"

        # real progress on the shrunk world, checkpointed — the grow
        # must pick up *newer* state than the shrink restored
        app2 = sess.app
        dr.advance(app2, 1)
        sess.snapshot(block=True)

        gt = sup.grow(sh.dead)                    # the host came back
        assert gt.action.name == "GROW"
        assert sorted(sup.world) == sorted(sh.hosts), \
            f"{spec.family}: grow left world {sup.world}"
        app3 = sess.app
        assert app3 is not app2, "grow must rebuild the runner"
        at = dr.step_of(app3)
        assert at == half + 1, \
            f"{spec.family}: grow restored at {at}, wanted {half + 1}"
        dr.advance(app3, dr.total - at)
        got = dr.digest(app3)
    assert got == want, \
        f"{spec.family}: post-grow digest {got} != reference {want}"


class _GrowingApp:
    """Protocol citizen whose semantic state GROWS mid-run: a cold-tier
    entry first exists at step 3, so inside a delta chain its first
    appearance is a non-base manifest. Stands in for every app that
    allocates state lazily — optimizer moments on the first update, a
    serving engine's per-session tables."""
    kind = "conformance-growing"

    def __init__(self) -> None:
        self.step = 0
        self.base = np.zeros(64, np.float64)
        self.late: Optional[np.ndarray] = None

    def advance(self, n: int) -> None:
        for _ in range(n):
            self.step += 1
            self.base += float(self.step)
            if self.step >= 3:
                z = self.late if self.late is not None \
                    else np.full(32, 7.0)
                self.late = z * 1.25 + self.step

    def digest(self) -> str:
        h = hashlib.sha256(self.base.tobytes())
        if self.late is not None:
            h.update(self.late.tobytes())
        h.update(str(self.step).encode())
        return h.hexdigest()

    def checkpoint_state(self):
        up = UpperHalf()
        up.register("base", "params", {"b": self.base.copy()})
        if self.late is not None:
            up.register("late", "opt_state", {"z": self.late.copy()})
        up.register("step", "step", np.int64(self.step))
        return up

    def checkpoint_step(self) -> int:
        return self.step

    def job_meta(self) -> Dict[str, Any]:
        return {"kind": self.kind}

    def bind(self, restore) -> None:
        self.base = np.asarray(restore.tree("base")["b"],
                               np.float64).copy()
        self.late = (np.asarray(restore.tree("late")["z"],
                                np.float64).copy()
                     if restore.has("late") else None)
        self.step = int(restore.scalar("step"))
        restore.release()


@register_app_kind(_GrowingApp.kind)
def _restore_growing(restore) -> _GrowingApp:
    app = _GrowingApp()
    app.bind(restore)
    return app


def run_midchain(store: str) -> None:
    """An entry first introduced mid-chain — its first appearance is a
    non-base delta link — must checkpoint and restore bit-identically
    through the public API alone."""
    ref = _GrowingApp()
    ref.advance(6)
    want = ref.digest()
    with CheckpointSession(store, Policy(interval=1, chain=8,
                                         keep_last=8)) as sess:
        app = sess.attach(_GrowingApp())
        for _ in range(4):
            app.advance(1)
            sess.maybe_snapshot()
        sess.wait()
        assert app.late is not None, \
            "the late entry must exist before the kill for the cell " \
            "to exercise a mid-chain introduction"
        del app                                   # hard kill
        app2 = sess.restore("latest")
        assert app2.step == 4, f"restored at step {app2.step}, wanted 4"
        app2.advance(2)
        got = app2.digest()
    assert got == want, \
        f"mid-chain-new-entry: digest {got} != reference {want}"


def tear_last_commit(store: str) -> int:
    """Recreate the crash-during-commit disk state, byte for byte.

    The protocol writes blobs first, then the manifest via temp-file +
    fsync + rename; a crash between those leaves the manifest as an
    uncommitted temp file. Renaming the newest committed manifest to a
    temp-style name IS that state (the backends' startup sweep ignores
    young temp files). Returns the torn step number."""
    _, path, _ = parse_store_spec(store)
    cands: List[str] = []
    for sub in ("manifests", "coordinator"):    # localfs / sharded layout
        cands += glob.glob(os.path.join(path, sub, "step_*.json"))
    assert cands, f"no committed manifests under {path}"
    latest = max(cands)                  # zero-padded: lexicographic order
    d, name = os.path.split(latest)
    os.rename(latest, os.path.join(d, ".tmp_crash_" + name))
    return int(name[len("step_"):-len(".json")])


def run_commit(spec: FamilySpec, store: str) -> None:
    """Crash during commit → reopen → torn step invisible, previous
    step restores, continuation bit-identical, store still writable."""
    dr = spec.train
    want = reference_digest(spec)
    policy = Policy(chain=2, keep_last=4, async_save=False)
    with CheckpointSession(store, policy) as sess:
        app = sess.attach(dr.fresh())
        dr.advance(app, dr.interval)
        sess.snapshot(block=True)
        survivor = dr.step_of(app)
        dr.advance(app, dr.interval)
        sess.snapshot(block=True)
        assert sess.latest_step() == dr.step_of(app)
        del app

    torn = tear_last_commit(store)

    with CheckpointSession(store, policy) as sess:
        steps = sess.restorable_steps()
        assert torn not in steps and survivor in steps, \
            f"{spec.family}: reopened store sees {steps}; torn step " \
            f"{torn} must be invisible, {survivor} restorable"
        app2 = sess.restore("latest", **dr.restore_kwargs())
        at = dr.step_of(app2)
        assert at == survivor, \
            f"{spec.family}: restored at {at}, wanted {survivor}"
        dr.advance(app2, dr.total - at)
        got = dr.digest(app2)
        assert got == want, \
            f"{spec.family}: post-reopen digest {got} != reference {want}"
        sess.snapshot(block=True)        # the torn file is inert: the
        assert sess.latest_step() == dr.total  # store still commits


def run_swap(spec: FamilySpec, store_a: str, store_b: str) -> None:
    """The full kill sequence under BOTH checkpoint packages — swapping
    is one spec string — with digests identical across packages."""
    da = _KILL.get((spec.family, store_a.split(":", 1)[0]))
    if da is None:
        da = run_kill(spec, store_a)
    db = run_kill(spec, store_b)
    assert da == db == reference_digest(spec), \
        f"{spec.family}: packages disagree ({da} vs {db})"

"""CheckpointSession: the one checkpoint-agnostic lifecycle.

The acceptance case for the API redesign: an app implemented ONLY
against ``repro.api`` (the streaming aggregator example) is killed
mid-run and restored to identical state through the app-kind registry;
the legacy ``Trainer.restore``/``ServingEngine.restore`` entry points
are thin shims over the same session API; the supervisor drives apps
only through protocol hooks."""
import os
import sys

import numpy as np
import pytest

from repro.api import (CheckpointSession, Policy, PolicyError,
                       UpperHalf, register_app_kind)

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")
if EXAMPLES not in sys.path:
    sys.path.insert(0, EXAMPLES)

import checkpointable_pipeline as cp  # noqa: E402  (registers its kind)


# --- a minimal in-test app ---------------------------------------------------

class TinyApp:
    """Smallest possible protocol citizen (numpy state, no model)."""

    def __init__(self, kind="tiny"):
        self.kind = kind
        self.x = np.zeros(4, np.float64)
        self.n = 0

    def step(self):
        self.x += np.arange(4) + self.n
        self.n += 1

    def checkpoint_state(self):
        up = UpperHalf()
        up.register("x", "x", self.x.copy())
        up.register("n", "step", np.int64(self.n))
        return up

    def checkpoint_step(self):
        return self.n

    def job_meta(self):
        return {"kind": self.kind}

    def bind(self, restore):
        self.x = np.asarray(restore.tree("x"), np.float64).copy()
        self.n = int(restore.scalar("n"))
        restore.release()


@register_app_kind("tiny")
def _restore_tiny(restore):
    app = TinyApp()
    app.bind(restore)
    return app


# --- the acceptance round-trip ----------------------------------------------

@pytest.mark.parametrize("scheme", ["localfs", "sharded"])
def test_pipeline_app_kill_restore_identical(tmp_path, scheme):
    """Kill the example app mid-run; restore lands on identical
    aggregation state and the finished run matches an uninterrupted
    one — under BOTH checkpoint packages (spec is a one-string swap)."""
    ref = cp.StreamAggregator(n_bins=8, seed=3)
    ref.ingest(60)

    suffix = "?hosts=3" if scheme == "sharded" else ""
    with CheckpointSession(f"{scheme}:{tmp_path}{suffix}",
                           Policy(interval=7, chain=3)) as sess:
        app = sess.attach(cp.StreamAggregator(n_bins=8, seed=3))
        for _ in range(30):
            app.ingest(1)
            sess.maybe_snapshot()
        sess.wait()
        mid_counts = app.counts.copy()
        mid_cursor = app.cursor
        del app   # crash

        app2 = sess.restore("latest")
        assert isinstance(app2, cp.StreamAggregator)
        assert app2.cursor == 28 <= mid_cursor   # last interval boundary
        # identical aggregation state at the restored cursor
        probe = cp.StreamAggregator(n_bins=8, seed=3)
        probe.ingest(app2.cursor)
        assert app2.digest() == probe.digest()
        np.testing.assert_array_equal(
            app2.counts + 0, probe.counts)  # arrays, not just digest
        app2.ingest(60 - app2.cursor)
        assert app2.digest() == ref.digest()
        assert not np.array_equal(mid_counts, probe.counts) or \
            mid_cursor == app2.cursor  # the kill really lost progress


def test_example_imports_only_the_api():
    """The agnosticism proof is only a proof if the example can't cheat:
    no repro.core (or deeper) import anywhere in its source."""
    src = open(os.path.join(EXAMPLES, "checkpointable_pipeline.py")).read()
    imports = [ln for ln in src.splitlines()
               if ln.lstrip().startswith(("import ", "from "))]
    offenders = [ln for ln in imports
                 if "repro" in ln and "repro.api" not in ln]
    assert not offenders, offenders
    assert any("repro.api" in ln for ln in imports)


# --- protocol validation + cadence ------------------------------------------

def test_attach_rejects_non_protocol_object(tmp_path):
    sess = CheckpointSession(f"localfs:{tmp_path}")
    try:
        with pytest.raises(PolicyError, match="checkpoint_state"):
            sess.attach(object())
    finally:
        sess.close()


def test_attach_requires_kind_in_job_meta(tmp_path):
    class NoKind(TinyApp):
        def job_meta(self):
            return {}

    sess = CheckpointSession(f"localfs:{tmp_path}")
    try:
        with pytest.raises(PolicyError, match="kind"):
            sess.attach(NoKind())
    finally:
        sess.close()


def test_snapshot_without_app_is_actionable(tmp_path):
    sess = CheckpointSession(f"localfs:{tmp_path}")
    try:
        with pytest.raises(PolicyError, match="attach"):
            sess.snapshot()
    finally:
        sess.close()


def test_maybe_snapshot_cadence(tmp_path):
    with CheckpointSession(f"localfs:{tmp_path}",
                           Policy(interval=3, async_save=False)) as sess:
        app = sess.attach(TinyApp())
        for _ in range(7):
            app.step()
            sess.maybe_snapshot()
        assert sess.backend.list_steps() == [3, 6]
        sess.maybe_snapshot(final=True)
        assert sess.backend.list_steps() == [3, 6, 7]


def test_restore_unknown_kind_is_actionable(tmp_path):
    with CheckpointSession(f"localfs:{tmp_path}",
                           Policy(async_save=False)) as sess:
        app = TinyApp(kind="never-registered")
        sess.attach(app)
        app.step()
        sess.snapshot(block=True)
        with pytest.raises(PolicyError, match="register_app_kind"):
            sess.restore("latest")


def test_expect_kind_guard(tmp_path):
    with CheckpointSession(f"localfs:{tmp_path}",
                           Policy(async_save=False)) as sess:
        app = sess.attach(TinyApp())
        app.step()
        sess.snapshot(block=True)
        with pytest.raises(PolicyError, match="not a serving checkpoint"):
            sess.restore("latest", expect_kind="serving")


# --- the legacy shims delegate to the session API ---------------------------

def test_trainer_restore_shim_delegates(monkeypatch):
    from repro.train.loop import Trainer
    calls = {}

    def fake_restore(self, step=None, **kw):
        calls["step"] = step
        calls.update(kw)
        return "the-trainer"

    monkeypatch.setattr(CheckpointSession, "restore", fake_restore)

    class FakeMgr:
        backend = None

    with pytest.warns(DeprecationWarning, match="CheckpointSession"):
        out = Trainer.restore(FakeMgr(), step=7, decode_workers=2)
    assert out == "the-trainer"
    assert calls["step"] == 7
    assert calls["expect_kind"] == "train"
    assert calls["workers"] == 2


def test_engine_restore_shim_delegates(monkeypatch):
    from repro.serving.engine import ServingEngine
    calls = {}

    def fake_restore(self, step=None, **kw):
        calls["step"] = step
        calls.update(kw)
        return "the-engine"

    monkeypatch.setattr(CheckpointSession, "restore", fake_restore)

    class FakeMgr:
        backend = None

    with pytest.warns(DeprecationWarning, match="CheckpointSession"):
        out = ServingEngine.restore(FakeMgr(), params={"p": 1}, n_slots=3)
    assert out == "the-engine"
    assert calls["expect_kind"] == "serving"
    assert calls["n_slots"] == 3
    assert calls["params"] == {"p": 1}


def test_tiny_app_save_restore_through_manager_session(tmp_path):
    """from_manager adopts an existing CheckpointManager — the shim
    construction path — and the round trip still works."""
    from repro.core import CheckpointManager, LocalFSBackend
    mgr = CheckpointManager(LocalFSBackend(str(tmp_path)),
                            async_save=False)
    sess = CheckpointSession.from_manager(mgr)
    app = sess.attach(TinyApp())
    app.step()
    app.step()
    sess.snapshot(block=True)
    app2 = CheckpointSession.from_manager(mgr).restore()
    assert app2.n == 2
    np.testing.assert_array_equal(app2.x, app.x)


# --- the supervisor drives apps only through protocol hooks ------------------

def test_supervisor_quiesce_hook_runs_at_teardown(tmp_path):
    with CheckpointSession(f"localfs:{tmp_path}",
                           Policy(async_save=False)) as sess:
        app = sess.attach(cp.StreamAggregator(n_bins=4, seed=0))
        app.ingest(3)
        sess.snapshot(block=True)
        restored = []
        sup = sess.supervise([0], heartbeat_timeout=1.0,
                             on_restored=lambda a, t: restored.append(a))
        assert sup.runner is app
        sup._recover(_fake_target())
        assert app.quiesced == 1           # protocol hook, not duck luck
        assert restored and restored[0].cursor == 3
        assert sup.runner is restored[0]
        assert sess.app is restored[0]     # session follows the swap


def _fake_target():
    from repro.core.supervisor import RestoreTarget
    from repro.core.failure import FailureAction
    return RestoreTarget(FailureAction.RESTART_LAST_CKPT, step=None,
                         hosts=[0])

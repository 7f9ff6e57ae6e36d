"""Restoring a delta chain, and the ``cached:`` store tier.

``materialize_manifest_chain`` decodes each leaf from the start of its
xor run forward, serially with ``workers=1`` and on its leaf and chunk
pools otherwise. Each branch must give back exactly the state saved at
the restored step, across a delta chain, with an entry introduced
mid-chain, and must fail loudly where the chain or a blob is broken.
The ``workers=`` knob threads from the public session API down to the
manager, and the ``cached:`` read-through store serves a second
restore from local bytes."""
import numpy as np
import pytest

from repro.api import (CheckpointSession, Policy, PolicyError, UpperHalf,
                       parse_store_spec, register_app_kind, resolve_backend)
from repro.api.errors import RestoreError
from repro.core import CheckpointManager, OpLog
from repro.core import delta as deltamod
from repro.core.backends.cached import CachedBackend
from repro.core.backends.localfs import LocalFSBackend

# the serial branch and the pooled one (leaf pool + chunk pool)
WORKERS = [1, 8]


def _upper(seed=0, n=20_000):
    rng = np.random.RandomState(seed)
    up = UpperHalf()
    up.register("params", "params",
                {"w": rng.randn(n).astype(np.float32),
                 "b": rng.randn(128).astype(np.float32)})
    up.register("opt_state", "opt_state",
                {"m": rng.randn(n).astype(np.float32),
                 "v": rng.randn(n).astype(np.float32)})
    up.register("step", "step", np.int64(seed))
    return up


def _save_chain(backend, steps=3, base_interval=4):
    """A delta chain: steps after the base xor-encode against it.
    -> (manager, {step: the payload saved at that step})."""
    mgr = CheckpointManager(backend, async_save=False,
                            delta_base_interval=base_interval)
    rng = np.random.RandomState(42)
    up = _upper(1)
    saved = {}
    for s in range(1, steps + 1):
        # perturb a slice so deltas are small but real
        w = up.get("params")["w"]
        w[rng.randint(0, len(w), 64)] += 0.5
        up.register("step", "step", np.int64(s))
        mgr.save(s, up, OpLog())
        saved[s] = up.to_host()
    return mgr, saved


def _assert_entries(want, got):
    """Restored entries ({entry: {path: array}}) equal ``want`` exactly."""
    assert set(got) == set(want)
    for name, by_path in want.items():
        assert set(got[name]) == set(by_path), name
        for path, v in by_path.items():
            np.testing.assert_array_equal(np.asarray(got[name][path]),
                                          np.asarray(v))


def _entry_blobs(manifest, entry):
    out = set()
    for meta in manifest["entries"][entry]["leaves"].values():
        out |= set(deltamod.leaf_blob_names(meta))
    return out


# --- bit-identity with what was saved ----------------------------------------

@pytest.mark.parametrize("workers", WORKERS)
@pytest.mark.parametrize("step", [1, 3])
def test_restore_matches_saved_across_delta_chain(tmp_path, step, workers):
    """The base step and the deepest xor step both restore to the state
    saved at that step (localfs, chain of 3)."""
    mgr, saved = _save_chain(LocalFSBackend(str(tmp_path)), steps=3)
    assert mgr.backend.get_manifest(3).get("base_step") is not None, \
        "step 3 must be a delta link for the cell to bite"
    _assert_entries(saved[step], mgr.restore(step, workers=workers).entries)


@pytest.mark.parametrize("workers", WORKERS)
def test_missing_blob_fails_loudly(tmp_path, workers):
    """A blob the store cannot serve fails the restore, naming the
    blob — never a silent zero tensor."""
    be = LocalFSBackend(str(tmp_path))
    _save_chain(be, steps=1)
    victim = sorted(_entry_blobs(be.get_manifest(1), "params"))[0]
    (be.root / "blobs" / victim[:2] / victim).unlink()
    mgr = CheckpointManager(be, async_save=False)
    with pytest.raises(FileNotFoundError, match=victim):
        mgr.restore(1, workers=workers)


# --- chains written by someone else ------------------------------------------

@pytest.mark.parametrize("workers", WORKERS)
def test_entry_introduced_mid_chain_matches_saved(tmp_path, workers):
    """An entry that first appears in a non-base link (the writer
    encodes its first appearance as ``full``) restores to the saved
    state: the run-start walk stops at the introduction instead of
    KeyError-ing off the chain's base."""
    be = LocalFSBackend(str(tmp_path))
    mgr = CheckpointManager(be, async_save=False, delta_base_interval=8)
    rng = np.random.RandomState(7)
    up = _upper(1)
    mgr.save(1, up, OpLog())
    up.register("late", "opt_state", {"z": rng.randn(256).astype(np.float32)})
    for s in (2, 3):
        up.get("params")["w"][rng.randint(0, 20_000, 64)] += 0.5
        up.get("late")["z"][rng.randint(0, 256, 16)] += 1.0
        up.register("step", "step", np.int64(s))
        mgr.save(s, up, OpLog())
    m3 = be.get_manifest(3)
    assert m3["entries"]["late"]["leaves"]["['z']"].get("mode") == "xor", \
        "the introduced entry must ride a delta link for the cell to bite"
    _assert_entries(up.to_host(), mgr.restore(3, workers=workers).entries)


@pytest.mark.parametrize("workers", WORKERS)
def test_foreign_chain_missing_mid_link_fails_loudly_per_leaf(tmp_path,
                                                             workers):
    """A chain whose mid manifest lacks an entry a later link xor's
    against (a foreign writer, a hand-damaged store) fails with a
    RestoreError naming what the leaf needed, not a KeyError; skipping
    the broken entry restores the rest as saved."""
    import glob as globmod
    import json
    be = LocalFSBackend(str(tmp_path))
    mgr, saved = _save_chain(be, steps=3)
    mid = sorted(globmod.glob(str(be.root / "manifests" / "step_*.json")))[1]
    with open(mid) as f:
        m = json.load(f)
    del m["entries"]["opt_state"]
    with open(mid, "w") as f:
        json.dump(m, f)

    with pytest.raises(RestoreError, match="base-step"):
        mgr.restore(3, workers=workers)
    rest = mgr.restore(3, workers=workers, skip_entries=("opt_state",))
    want = {k: v for k, v in saved[3].items() if k != "opt_state"}
    _assert_entries(want, rest.entries)


# --- workers= threads through the public API --------------------------------

def test_session_threads_workers(tmp_path, monkeypatch):
    import repro.core.checkpoint as ckpt
    seen = {}
    orig = ckpt.CheckpointManager.restore

    def spy(self, *a, **kw):
        seen.update(kw)
        return orig(self, *a, **kw)

    monkeypatch.setattr(ckpt.CheckpointManager, "restore", spy)
    with CheckpointSession(f"localfs:{tmp_path}", Policy()) as sess:
        app = sess.attach(_TinyOpt())
        app.step()
        sess.snapshot(block=True)
        del app
        app2 = sess.restore("latest", workers=3)
    assert seen["workers"] == 3
    assert app2.n == 1


class _TinyOpt:
    """Protocol citizen with params, optimizer moments and a step."""
    kind = "tinyopt"

    def __init__(self):
        self.x = np.zeros(8, np.float64)
        self.m = np.zeros(8, np.float64)
        self.n = 0

    def step(self):
        self.x += 1.0
        self.m = 0.9 * self.m + self.x
        self.n += 1

    def checkpoint_state(self):
        up = UpperHalf()
        up.register("x", "params", self.x.copy())
        up.register("opt_state", "opt_state", {"m": self.m.copy()})
        up.register("n", "step", np.int64(self.n))
        return up

    def checkpoint_step(self):
        return self.n

    def job_meta(self):
        return {"kind": self.kind}

    def bind(self, restore):
        self.x = np.asarray(restore.tree("x"), np.float64).copy()
        self.m = np.asarray(restore.tree("opt_state")["m"],
                            np.float64).copy()
        self.n = int(restore.scalar("n"))
        restore.release()


@register_app_kind("tinyopt")
def _restore_tinyopt(restore):
    app = _TinyOpt()
    app.bind(restore)
    return app


# --- the cached: tier --------------------------------------------------------

def test_parse_store_spec_nested_over():
    scheme, path, params = parse_store_spec(
        "cached:/ssd/cache?over=sharded:/remote?hosts=4&replicate=1")
    assert (scheme, path) == ("cached", "/ssd/cache")
    # everything after over= belongs to the inner spec, verbatim
    assert params == {"over": "sharded:/remote?hosts=4&replicate=1"}


def test_cached_needs_over():
    with pytest.raises(PolicyError, match="cached"):
        resolve_backend("cached:/tmp/nowhere")


def test_cached_warms_then_serves_locally(tmp_path):
    """First restore reads through (misses, warms); second restore is
    served from the cache — the inner store sees no blob reads."""
    remote = tmp_path / "remote"
    cache = tmp_path / "cache"
    _, saved = _save_chain(LocalFSBackend(str(remote)), steps=1)

    spec = f"cached:{cache}?over=localfs:{remote}"
    cb = resolve_backend(spec)
    assert isinstance(cb, CachedBackend)
    first = CheckpointManager(cb, async_save=False).restore(1)
    assert cb.stats["warmed"] > 0 and cb.stats["hits"] == 0
    _assert_entries(saved[1], first.entries)

    class _Dead:
        def get_blob(self, name):
            raise AssertionError(f"cache miss leaked to remote: {name}")

        def __getattr__(self, attr):
            return getattr(cb.inner, attr)

    cb2 = CachedBackend(str(cache), _Dead())
    second = CheckpointManager(cb2, async_save=False).restore(1)
    _assert_entries(saved[1], second.entries)
    assert cb2.stats["hits"] > 0 and cb2.stats["misses"] == 0


def test_cached_writes_through(tmp_path):
    """Snapshots taken through the cached front land durably in the
    inner store (cache loss must never lose data)."""
    spec = (f"cached:{tmp_path / 'c'}?over=sharded:{tmp_path / 'r'}"
            "?hosts=2&replicate=1")
    cb = resolve_backend(spec)
    mgr = CheckpointManager(cb, async_save=False)
    mgr.save(1, _upper(9), OpLog())
    # the inner store alone can serve the checkpoint
    inner_only = CheckpointManager(cb.inner, async_save=False)
    got = inner_only.restore(1)
    np.testing.assert_array_equal(
        np.asarray(got.entries["step"][""]), np.int64(9))

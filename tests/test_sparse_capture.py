"""Device-resident dirty-chunk capture: the sparse capture/encode
contract (manifest format 3) — fingerprint dirty detection, identity
skips, sparse chain application, failure re-baselining, and equivalence
with the dense format-2 path.

Tests use a small ``sparse_chunk_bytes`` so modest arrays span many
chunks; the production default is 256 KiB (kernels/ckpt_codec/ref.py).
"""
import numpy as np
import pytest

from repro.core import (CheckpointManager, Incarnation, LocalFSBackend,
                        OpLog, UpperHalf)
from repro.core import delta as deltamod
from repro.core.restore import restorable_steps

CB = 4096  # sparse chunk bytes for tests


def _mgr(path, **kw):
    kw.setdefault("async_save", False)
    kw.setdefault("delta_base_interval", 8)
    kw.setdefault("sparse_chunk_bytes", CB)
    kw.setdefault("sparse_min_bytes", 2 * CB)
    return CheckpointManager(LocalFSBackend(str(path)), **kw)


def _upper(rng, n=64 * 1024):
    up = UpperHalf()
    up.register("params", "params",
                {"w": rng.randn(n).astype(np.float32),
                 "b": rng.randn(64).astype(np.float32)})  # below min: dense
    up.register("step", "step", np.int64(0))
    return up


# ---------------------------------------------------------------------------
# sparse chain roundtrip + manifest shape
# ---------------------------------------------------------------------------

def test_sparse_chain_roundtrip_bit_identical(tmp_path):
    """Scattered in-place updates: every link is a sparse format-3
    manifest recording only dirty chunks, and every step restores to
    the exact bytes that were live at its capture."""
    rng = np.random.RandomState(0)
    mgr = _mgr(tmp_path)
    up = _upper(rng)
    want = {}
    for s in range(1, 7):
        w = up.get("params")["w"]
        idx = rng.randint(0, w.size, size=40)
        w[idx] += rng.randn(idx.size).astype(np.float32)
        up.update("step", np.int64(s))
        mgr.save(s, up, OpLog())
        want[s] = w.copy()

    be = mgr.backend
    assert be.get_manifest(1)["format"] == 2      # full base, no sparse
    for s in range(2, 7):
        m = be.get_manifest(s)
        assert m["format"] == 3
        raw = m["entries"]["params"]["leaves"]["['w']"]["parts"]["raw"]
        assert raw["chunk_bytes"] == CB
        assert 0 < len(raw["dirty"]) < raw["n_chunks"]
    assert mgr.stats["dirty_chunks"] > 0
    assert mgr.stats["clean_chunks"] > mgr.stats["dirty_chunks"]

    for s in range(1, 7):
        r = mgr.restore(s)
        np.testing.assert_array_equal(r.entries["params"]["['w']"], want[s])
        assert int(r.entries["step"][""]) == s


def test_sparse_capture_moves_fewer_bytes_than_dense(tmp_path):
    """The point of the PR: capture traffic and encode work scale with
    the change rate, not the state size."""
    results = {}
    for sparse in (True, False):
        rng = np.random.RandomState(1)
        mgr = _mgr(tmp_path / str(sparse), sparse_capture=sparse)
        up = _upper(rng, n=128 * 1024)
        mgr.save(1, up, OpLog())
        base = dict(mgr.stats)
        for s in (2, 3, 4):
            w = up.get("params")["w"]
            w[:w.size // 50] += 1.0   # ~2% of chunks dirty
            mgr.save(s, up, OpLog())
        results[sparse] = {
            k: mgr.stats[k] - base[k]
            for k in ("capture_bytes", "bytes_encoded")}
        r = mgr.restore(4)
        np.testing.assert_array_equal(r.entries["params"]["['w']"],
                                      up.get("params")["w"])
    assert results[True]["capture_bytes"] < \
        results[False]["capture_bytes"] / 4
    assert results[True]["bytes_encoded"] < \
        results[False]["bytes_encoded"] / 4


def test_identity_skip_for_immutable_jax_leaves(tmp_path):
    """A leaf that is the same jax Array object as last capture is
    skipped without reading a byte (immutability makes identity a proof
    of byte-equality); restores stay exact."""
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.RandomState(2)
    frozen = jnp.asarray(rng.randn(16 * 1024).astype(np.float32))
    mgr = _mgr(tmp_path)
    up = UpperHalf()
    hot0 = rng.randn(16 * 1024).astype(np.float32)
    up.register("params", "params", {"frozen": frozen, "hot": None})
    for s in (1, 2, 3):
        hot = hot0.copy()
        hot[::101] += s
        up.update("params", {"frozen": frozen, "hot": jnp.asarray(hot)})
        mgr.save(s, up, OpLog())
    assert mgr.stats["identity_skips"] == 2   # frozen at steps 2 and 3
    r = mgr.restore(3)
    np.testing.assert_array_equal(r.entries["params"]["['frozen']"],
                                  np.asarray(frozen))
    np.testing.assert_array_equal(r.entries["params"]["['hot']"], hot)


def test_gc_keeps_sparse_chain_blobs(tmp_path):
    """referenced_hashes must see sparse dirty-chunk blobs, or GC would
    tear restorable chains apart."""
    rng = np.random.RandomState(3)
    mgr = _mgr(tmp_path, keep_last=2)
    up = _upper(rng)
    for s in range(1, 5):
        up.get("params")["w"][:64] += 1.0
        mgr.save(s, up, OpLog())
        want = up.get("params")["w"].copy()
    assert restorable_steps(mgr.backend) == [1, 2, 3, 4]
    r = mgr.restore(4)
    np.testing.assert_array_equal(r.entries["params"]["['w']"], want)


def test_encode_failure_rebaselines_chain(tmp_path):
    """A snapshot that dies mid-commit invalidates the fingerprint
    baseline: the next snapshot is a dense full base (no sparse capture
    may XOR against a half-patched mirror), and the chain then
    resumes."""
    class Crashing(LocalFSBackend):
        crash = False

        def put_blob(self, name, data):
            if self.crash:
                raise OSError("injected crash")
            super().put_blob(name, data)

    be = Crashing(str(tmp_path))
    mgr = CheckpointManager(be, async_save=False, delta_base_interval=8,
                            sparse_chunk_bytes=CB, sparse_min_bytes=2 * CB)
    rng = np.random.RandomState(4)
    up = _upper(rng)
    mgr.save(1, up, OpLog())
    up.get("params")["w"][:32] += 1.0
    be.crash = True
    with pytest.raises(OSError, match="injected crash"):
        mgr.save(2, up, OpLog())
    be.crash = False
    up.get("params")["w"][100:132] += 1.0
    mgr.save(3, up, OpLog())
    m3 = be.get_manifest(3)
    assert m3["base_step"] is None and m3["format"] == 2
    np.testing.assert_array_equal(mgr.restore(3).entries["params"]["['w']"],
                                  up.get("params")["w"])
    up.get("params")["w"][200:232] += 1.0
    mgr.save(4, up, OpLog())
    assert be.get_manifest(4)["base_step"] == 3   # chain resumed
    np.testing.assert_array_equal(mgr.restore(4).entries["params"]["['w']"],
                                  up.get("params")["w"])


def test_format2_checkpoint_restores_through_incarnation(tmp_path):
    """Backward compatibility: a dense format-2 chain written with
    sparse capture disabled restores through the Incarnation lifecycle
    unchanged."""
    rng = np.random.RandomState(5)
    mgr = _mgr(tmp_path, sparse_capture=False)
    up = _upper(rng)
    for s in (1, 2):
        up.get("params")["w"][:128] += 1.0
        up.update("step", np.int64(s))
        mgr.save(s, up, OpLog())
    assert mgr.backend.get_manifest(2)["format"] == 2
    inc = Incarnation(mgr, step=2)
    state = inc.materialize()
    inc.build_lower()   # empty log: fresh, hardware-free lower half
    np.testing.assert_array_equal(state.entries["params"]["['w']"],
                                  up.get("params")["w"])
    assert int(inc.scalar("step")) == 2


def test_unknown_manifest_format_is_rejected(tmp_path):
    """A manifest from a newer build fails loudly instead of being
    silently misread."""
    rng = np.random.RandomState(6)
    mgr = _mgr(tmp_path)
    mgr.save(1, _upper(rng), OpLog())
    m = mgr.backend.get_manifest(1)
    m["format"] = 99
    mgr.backend.commit_manifest(1, m)
    with pytest.raises(ValueError, match="format 99"):
        mgr.restore(1)


def test_invalid_sparse_chunk_bytes_rejected_at_construction(tmp_path):
    """Unsupported chunk geometry fails with a clear ValueError when the
    manager is built — not an AssertionError inside the first save."""
    with pytest.raises(ValueError, match="sparse_chunk_bytes"):
        _mgr(tmp_path, sparse_chunk_bytes=12 * 1024)   # not a seg multiple
    with pytest.raises(ValueError, match="sparse_chunk_bytes"):
        _mgr(tmp_path, sparse_chunk_bytes=100)         # not a lane multiple


def test_vanished_leaf_cannot_match_stale_baseline(tmp_path):
    """A leaf that disappears for one snapshot and reappears must not
    sparse-encode against a mirror that no longer holds it."""
    rng = np.random.RandomState(7)
    mgr = _mgr(tmp_path)
    w = rng.randn(16 * 1024).astype(np.float32)
    up = UpperHalf()
    up.register("params", "params", {"w": w.copy()})
    mgr.save(1, up, OpLog())
    up.update("params", {})                    # leaf vanishes
    mgr.save(2, up, OpLog())
    up.update("params", {"w": w.copy()})       # reappears, same bytes
    mgr.save(3, up, OpLog())
    r = mgr.restore(3)
    np.testing.assert_array_equal(r.entries["params"]["['w']"], w)


# ---------------------------------------------------------------------------
# SnapshotHandle.result(timeout) regression
# ---------------------------------------------------------------------------

def test_result_timeout_raises_builtin_timeout_error(tmp_path):
    """result(timeout) on an uncommitted snapshot raises the builtin
    TimeoutError — never returns partial state, and is catchable by
    ``except TimeoutError`` on every Python version."""
    import time

    class Slow(LocalFSBackend):
        def put_blob(self, name, data):
            time.sleep(0.2)
            super().put_blob(name, data)

    rng = np.random.RandomState(8)
    mgr = CheckpointManager(Slow(str(tmp_path)), async_save=True)
    up = _upper(rng, n=256 * 1024)
    h = mgr.save(1, up, OpLog())
    with pytest.raises(TimeoutError, match="step 1"):
        h.result(timeout=0.01)
    manifest = h.result()             # eventually commits fine
    assert manifest["step"] == 1
    mgr.close()


def test_device_capture_chain_with_read_only_staging(tmp_path, monkeypatch):
    """The capture path of an attached TPU, with the Pallas kernels in
    interpret mode: the staged copy of a jax leaf is the read-only
    buffer ``device_get`` returns, and it becomes the pinned mirror that
    a sparse link patches in place. Every link must still encode and
    every step restore bit-identically, through the fingerprint and
    fused kernels."""
    import jax.numpy as jnp
    from repro.core import async_snapshot
    monkeypatch.setattr(async_snapshot, "_BACKEND", "tpu")
    rng = np.random.RandomState(3)
    w = rng.randn(16 * CB // 4).astype(np.float32)
    mgr = _mgr(tmp_path, sparse_min_bytes=CB)
    up = UpperHalf()
    want = {}
    for s in range(1, 5):
        w[rng.randint(0, w.size, size=3)] += 1.0
        # jnp.array copies: on the CPU jnp.asarray may alias ``w``, and
        # the next step's in-place update would then race the base's
        # fingerprint kernel, which runs asynchronously on that buffer
        up.register("params", "params",
                    {"w": jnp.array(w), "b": jnp.array(w[:CB // 4])})
        mgr.save(s, up, OpLog())
        want[s] = w.copy()
    assert mgr.stats["fp_kernel_leaves"] == 2    # the base's baselines
    assert mgr.stats["fused_leaves"] == 6        # two leaves, three links
    assert mgr.stats["host_fp_leaves"] == 0
    for s, v in want.items():
        _, entries = async_snapshot.materialize_manifest_chain(
            mgr.backend, s)
        np.testing.assert_array_equal(entries["params"]["['w']"], v)

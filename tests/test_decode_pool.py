"""The restore's chunk pool (core/delta.py ``DecodePool``): the chunks of a
leaf's large parts decode on worker threads into disjoint slices of its
output, giving the arrays of the serial decode; a bad chunk fails the
restore, and no chunk task outlives it."""
import sys
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
import pytest

from repro.api.errors import RestoreError
from repro.core import (CheckpointManager, Incarnation, LocalFSBackend,
                        OpLog, UpperHalf, materialize_manifest_chain)
from repro.core import delta as deltamod

CB = 256 * 1024          # dense chunk size in these tests
SPARSE_CB = 64 * 1024    # sparse chunk size


class MemStore:
    """A backend of dicts: what ``materialize_manifest_chain`` reads."""

    def __init__(self):
        self.blobs, self.manifests = {}, {}

    def put_blob(self, h, data):
        self.blobs[h] = data

    def get_blob(self, h):
        return self.blobs[h]

    def get_manifest(self, step):
        return self.manifests[step]


def _bytes(v):
    return np.ascontiguousarray(v).reshape(-1).view(np.uint8)


def _values(rng):
    """Leaves of several dtypes: a partial tail chunk, all-zero chunks,
    compressible and incompressible bytes, and a one-chunk leaf."""
    a = rng.randint(0, 256, 9 * CB + 777, dtype=np.uint8)
    a[2 * CB:4 * CB] = 0
    b = np.repeat(rng.randn(3 * CB // 64).astype(np.float32), 16)
    h = rng.randn(5 * CB // 2 + 3).astype(ml_dtypes.bfloat16)
    one = rng.randint(0, 256, CB // 3, dtype=np.uint8)
    return {"a": a, "b": b, "h": h, "one": one}


def _mutate(vals, rng, link):
    """The next step's values: every third dense chunk changes (the first
    of them over a whole sparse chunk, so its delta is incompressible),
    the rest do not, and each leaf's last byte (its tail chunk) changes."""
    out = {}
    for name, v in vals.items():
        v = v.copy()
        vb = _bytes(v)
        for k, d in enumerate(range(link % 2, -(-vb.size // CB), 3)):
            off = d * CB + SPARSE_CB
            if k == 0 and off + SPARSE_CB <= vb.size:
                vb[off:off + SPARSE_CB] = rng.randint(0, 256, SPARSE_CB)
            else:
                vb[min(off + rng.randint(100), vb.size - 1)] ^= \
                    1 + rng.randint(255)
        vb[-1] ^= 0x5A
        out[name] = v
    return out


def _sparse_meta(cur, prev, put, has):
    """A format-3 link of ``cur`` against ``prev``, as capture would give
    it: the changed chunks plus one conservative mark, compacted."""
    cb, pb = _bytes(cur), _bytes(prev)
    n = -(-cb.size // SPARSE_CB)
    dirty = [i for i in range(n)
             if not np.array_equal(cb[i * SPARSE_CB:(i + 1) * SPARSE_CB],
                                   pb[i * SPARSE_CB:(i + 1) * SPARSE_CB])]
    dirty = sorted(set(dirty) | {0})
    compact = np.zeros((len(dirty), SPARSE_CB), np.uint8)
    for j, i in enumerate(dirty):
        row = cb[i * SPARSE_CB:(i + 1) * SPARSE_CB]
        compact[j, :row.size] = row
    meta = deltamod.encode_leaf_sparse(
        cur.shape, cur.dtype, SPARSE_CB, n, np.asarray(dirty, np.int64),
        compact, prev.copy(), put, has)
    return {k: v for k, v in meta.items()
            if k not in ("bytes_written", "bytes_encoded")}


def _build(kinds, seed=7):
    """A chain of manifests, one per kind: ``full`` or ``codec`` (a base),
    ``xor`` (a dense link) or ``sparse`` (a dirty-chunk link). Returns the
    store and the last step's values."""
    rng = np.random.RandomState(seed)
    store = MemStore()
    has = store.blobs.__contains__
    vals, prev = _values(rng), None
    for step, kind in enumerate(kinds, 1):
        if step > 1:
            prev, vals = vals, _mutate(vals, rng, step)
        leaves = {}
        for name, v in vals.items():
            if kind == "sparse":
                leaves[name] = _sparse_meta(v, prev[name], store.put_blob,
                                            has)
            else:
                leaves[name] = deltamod.encode_leaf(
                    v, store.put_blob, has,
                    codec="int8" if kind == "codec" else None,
                    prev=prev[name] if kind == "xor" else None)
        store.manifests[step] = {
            "format": 3, "base_step": step - 1 if step > 1 else None,
            "entries": {"params": {"leaves": leaves}}}
    return store, vals


def _restore(store, workers, timings=None):
    _, entries = materialize_manifest_chain(store, max(store.manifests),
                                            workers=workers, timings=timings)
    return entries["params"]


CHAINS = [("full",), ("codec",), ("full", "xor"), ("full", "sparse"),
          ("full", "xor", "sparse"), ("full", "sparse", "sparse"),
          ("full", "sparse", "xor")]


@pytest.mark.parametrize("kinds", CHAINS, ids="-".join)
def test_pooled_restore_is_bit_identical_to_serial(kinds, monkeypatch):
    """Every part kind and a base + two-link chain: the restore on eight
    workers gives the serial restore's arrays, bit for bit, and (but for
    the lossy codec) the saved values."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    store, want = _build(kinds)
    metas = store.manifests[len(kinds)]["entries"]["params"]["leaves"]
    encs = {e for m in metas.values() for p in m["parts"].values()
            for e in (p.get("enc") or [d[2] for d in p["dirty"]])}
    assert encs == {deltamod.ENC_RAW, deltamod.ENC_ZLIB}
    if kinds[-1] == "xor":           # unchanged chunks were elided
        assert None in metas["a"]["parts"]["raw"]["chunks"]
    t1, t8 = {}, {}
    serial, pooled = _restore(store, 1, t1), _restore(store, 8, t8)
    assert t1["decode_pool_chunks"] == 0 < t8["decode_pool_chunks"]
    assert set(serial) == set(pooled) == set(want)
    for path, got in pooled.items():
        assert got.dtype == serial[path].dtype
        assert got.shape == serial[path].shape
        np.testing.assert_array_equal(_bytes(got), _bytes(serial[path]))
    if kinds[0] != "codec":
        for name, v in want.items():
            np.testing.assert_array_equal(_bytes(pooled[name]),
                                          _bytes(v))


def test_decode_leaf_copies_prev_unless_patching(monkeypatch):
    """``decode_leaf`` leaves ``prev`` as it was by default; with
    ``patch_prev`` the value is written into ``prev``'s buffer. Pool or
    not, the value is the same."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    for kinds in (("full", "xor"), ("full", "sparse")):
        store, want = _build(kinds, seed=3)
        meta0 = store.manifests[1]["entries"]["params"]["leaves"]["a"]
        meta1 = store.manifests[2]["entries"]["params"]["leaves"]["a"]
        base = deltamod.decode_leaf(meta0, store.get_blob)
        kept = base.copy()
        got = deltamod.decode_leaf(meta1, store.get_blob, prev=base)
        np.testing.assert_array_equal(base, kept)
        np.testing.assert_array_equal(got, want["a"])
        with ThreadPoolExecutor(4) as ex:
            pool = deltamod.DecodePool(ex)
            got = deltamod.decode_leaf(meta1, store.get_blob, prev=base,
                                       pool=pool, patch_prev=True)
        assert np.shares_memory(got, base)
        np.testing.assert_array_equal(got, want["a"])
        assert pool.counts["decode_pool_chunks"] > 1


def _blob_of(store, step, enc):
    """(hash, enc) of the first stored chunk of leaf ``a`` at ``step``,
    of encoding ``enc`` if one is given."""
    part = store.manifests[step]["entries"]["params"]["leaves"]["a"][
        "parts"]["raw"]
    rows = part.get("dirty") or [(i, h, e) for i, (h, e) in
                                 enumerate(zip(part["chunks"], part["enc"]))]
    return next((h, e) for _, h, e in rows
                if h is not None and enc in (None, e))


@pytest.mark.parametrize("workers", [1, 8])
@pytest.mark.parametrize("which", ["raw", "zlib", "sparse"])
def test_a_short_chunk_raises_restore_error(which, workers, monkeypatch):
    """A chunk whose bytes (inflated, if stored so) are fewer than its
    slot's raises ``RestoreError``, on the pool as inline; it never
    leaves part of the output unwritten."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    store, _ = _build(("full", "sparse"))
    h, enc = (_blob_of(store, 2, None) if which == "sparse"
              else _blob_of(store, 1, which))
    data = store.blobs[h]
    store.blobs[h] = (zlib.compress(zlib.decompress(data)[:-100])
                      if enc == deltamod.ENC_ZLIB else data[:-100])
    with pytest.raises(RestoreError, match="decodes to"):
        _restore(store, workers)


def test_malformed_parts_raise_restore_error(monkeypatch):
    """A dense part that lists the wrong number of chunks, and a sparse
    part whose dirty chunks repeat or lie past its end, raise
    ``RestoreError`` before any chunk is decoded."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    store, _ = _build(("full", "sparse"))
    dense = store.manifests[1]["entries"]["params"]["leaves"]["a"][
        "parts"]["raw"]
    dense["chunks"].pop()
    dense["enc"].pop()
    with pytest.raises(RestoreError, match="lists"):
        _restore(store, 8)
    store, _ = _build(("full", "sparse"))
    sparse = store.manifests[2]["entries"]["params"]["leaves"]["a"][
        "parts"]["raw"]
    for bad in ([sparse["dirty"][0]] + sparse["dirty"],
                sparse["dirty"] + [[10 ** 6, sparse["dirty"][0][1], "raw"]]):
        good, sparse["dirty"] = sparse["dirty"], bad
        with pytest.raises(RestoreError, match="dirty chunk"):
            _restore(store, 8)
        sparse["dirty"] = good


class FailingReads:
    """A ``get_blob`` that raises on one blob, after a short read; it
    counts the reads begun and those still running."""

    def __init__(self, store, bad):
        self.real, self.bad = store.get_blob, bad
        self.lock = threading.Lock()
        self.active = self.calls = 0

    def __call__(self, h):
        with self.lock:
            self.active += 1
            self.calls += 1
        try:
            time.sleep(0.01)
            if h == self.bad:
                raise OSError("injected read failure")
            return self.real(h)
        finally:
            with self.lock:
                self.active -= 1

    def assert_quiet(self):
        """No read is running, and none starts from here on."""
        with self.lock:
            assert self.active == 0
            seen = self.calls
        time.sleep(0.1)
        assert self.calls == seen


@pytest.mark.parametrize("workers", [1, 8])
def test_a_failed_read_fails_the_restore_and_leaves_no_task(workers,
                                                            monkeypatch):
    """A ``get_blob`` that raises on one chunk fails
    ``materialize_manifest_chain`` with its own exception; by then every
    other chunk task has been cancelled or has finished, and the pools
    are gone."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    store, _ = _build(("full", "sparse"))
    store.get_blob = reads = FailingReads(store, _blob_of(store, 1, None)[0])
    with pytest.raises(OSError, match="injected"):
        _restore(store, workers)
    reads.assert_quiet()
    assert not [t for t in threading.enumerate()
                if t.name.startswith(("chain-chunk", "chain-decode"))]


def test_a_failed_chunk_task_cancels_the_rest(monkeypatch):
    """On an executor that lives on, a part whose first chunk fails to
    read raises from ``decode_leaf`` only once the chunks still queued
    are cancelled and those running have finished."""
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    store, _ = _build(("full",))
    meta = store.manifests[1]["entries"]["params"]["leaves"]["a"]
    reads = FailingReads(store, meta["parts"]["raw"]["chunks"][0])
    with ThreadPoolExecutor(2) as ex:
        pool = deltamod.DecodePool(ex)
        with pytest.raises(OSError, match="injected"):
            deltamod.decode_leaf(meta, reads, pool=pool)
        reads.assert_quiet()
        assert reads.calls < len(meta["parts"]["raw"]["chunks"])


def test_many_small_chunks_under_thread_switching(monkeypatch):
    """Hundreds of small chunk tasks on more workers than cores, with the
    interpreter switching threads as often as it can: the restore is
    still the saved value, and every chunk task is counted."""
    small = 4096
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", small)
    rng = np.random.RandomState(11)
    store = MemStore()
    v0 = rng.randint(0, 256, 300 * small + 5, dtype=np.uint8)
    v1 = v0.copy()
    v1[::3 * small // 2] ^= 0xFF
    m0 = deltamod.encode_leaf(v0, store.put_blob, store.blobs.__contains__)
    m1 = deltamod.encode_leaf(v1, store.put_blob, store.blobs.__contains__,
                              prev=v0)
    for step, m in ((1, m0), (2, m1)):
        store.manifests[step] = {"format": 3,
                                 "base_step": None if step == 1 else 1,
                                 "entries": {"params": {"leaves": {"v": m}}}}
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(3):
            t = {}
            got = _restore(store, 16, t)["v"]
            np.testing.assert_array_equal(got, v1)
            assert t["decode_pool_chunks"] == 2 * 301
    finally:
        sys.setswitchinterval(interval)


def test_restore_timings_count_work_not_the_wait(tmp_path):
    """On a slow store, a one-leaf restore on eight workers: the chunk
    tasks read in parallel, so ``fetch_s`` (their reads, summed) exceeds
    the wall time, the leaf's wait on them stays within
    ``materialize_s``, and ``decode_s`` holds none of that wait. With
    one worker nothing runs on a pool."""
    n_chunks = 4
    cb = deltamod.CHUNK_BYTES
    w = np.random.RandomState(5).randint(0, 256, n_chunks * cb - 9,
                                         np.uint8)
    mgr = CheckpointManager(LocalFSBackend(str(tmp_path)))
    up = UpperHalf()
    up.register("params", "params", {"w": w})
    mgr.save(1, up, OpLog())
    mgr.wait()
    real = mgr.backend.get_blob

    def slow(h):
        time.sleep(0.5)
        return real(h)

    mgr.backend.get_blob = slow
    try:
        inc = Incarnation(mgr, step=1, workers=8)
        got = inc.materialize().entries["params"]["['w']"]
        np.testing.assert_array_equal(got, w)
        t = inc.timings
        assert t["decode_pool_chunks"] == n_chunks
        assert 0 < t["decode_pool_wait_s"] <= t["materialize_s"]
        assert t["fetch_s"] >= n_chunks * 0.5 > t["materialize_s"]
        assert 0 <= t["decode_s"] < t["decode_pool_wait_s"]

        inc1 = Incarnation(mgr, step=1, workers=1)
        np.testing.assert_array_equal(
            inc1.materialize().entries["params"]["['w']"], w)
        t1 = inc1.timings
        assert t1["decode_pool_chunks"] == 0
        assert t1["decode_pool_wait_s"] == 0
        assert t1["fetch_s"] >= n_chunks * 0.5
    finally:
        mgr.close()

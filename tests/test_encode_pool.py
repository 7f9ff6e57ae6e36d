"""The encode's chunk pool (core/delta.py ``ChunkPool``): chunk transforms
on worker threads give the blobs and manifests of a serial encode, hold
at most the window's count of results, and a failing transform fails its
snapshot without hanging the pipeline."""
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import (AsyncSnapshotter, LocalFSBackend, OpLog, UpperHalf,
                        materialize_manifest_chain)
from repro.core import delta as deltamod

CB = 256 * 1024          # dense chunk size in these tests (4x the probe)
SPARSE_CB = 64 * 1024    # sparse chunk size
WINDOW = 3


@pytest.fixture
def executor():
    ex = ThreadPoolExecutor(max_workers=4, thread_name_prefix="test-chunk")
    yield ex
    ex.shutdown(wait=True)


def _chunks(rng, kinds, cb):
    """A byte buffer of one chunk per kind: "rand" (incompressible),
    "smooth" (compressible), "zero", or an int i (a copy of chunk i)."""
    out = []
    for k in kinds:
        if k == "rand":
            out.append(rng.randint(0, 256, cb, dtype=np.uint8))
        elif k == "smooth":
            out.append(np.repeat(rng.randint(0, 4, cb // 64, dtype=np.uint8),
                                 64))
        elif k == "zero":
            out.append(np.zeros(cb, np.uint8))
        else:
            out.append(out[k].copy())
    return np.concatenate(out)


def _leaves(mode, rng):
    """(name, kwargs for the encode, the sparse leaf's current bytes) per
    leaf, in encode order: repeated
    chunks inside one leaf and across leaves, a leaf with a partial tail
    chunk, and a one-chunk leaf last."""
    kinds = ["rand", "zero", 0, "smooth", "rand", 3, "zero", "smooth",
             "rand", 0]
    if mode == "sparse":
        n = 12
        prev = _chunks(rng, ["rand"] * n, SPARSE_CB)
        out = []
        for name, dirty, tail in (("a", [0, 1, 2, 5, 6, 9, 11], 0),
                                  ("b", [1, 2, 3, 7, 11], 1000),
                                  ("one", [4], 0)):
            p = prev[:n * SPARSE_CB - tail].copy()
            cur = p.copy()
            delta = _chunks(rng, ["smooth", "smooth", "rand"], SPARSE_CB)
            for j, i in enumerate(dirty):
                off = i * SPARSE_CB
                ln = min(SPARSE_CB, p.size - off)
                # chunks 0 and 5 (and b's 1, 2) share a delta; one dirty
                # mark of each leaf is conservative: nothing changed
                if j != len(dirty) - 2:
                    src = delta[(j % 2) * SPARSE_CB:][:ln]
                    cur[off:off + ln] ^= src
            compact = np.zeros((len(dirty), SPARSE_CB), np.uint8)
            for j, i in enumerate(dirty):
                off = i * SPARSE_CB
                ln = min(SPARSE_CB, p.size - off)
                compact[j, :ln] = cur[off:off + ln]
            out.append((name, dict(shape=(p.size,), dtype=np.uint8,
                                   chunk_bytes=SPARSE_CB,
                                   n_chunks=-(-p.size // SPARSE_CB),
                                   dirty_idx=np.asarray(dirty, np.int64),
                                   dirty_bytes=compact, prev=p), cur))
        return out
    a = _chunks(rng, kinds, CB)
    b = np.concatenate([a[3 * CB:6 * CB], _chunks(rng, ["rand"], CB)[:777]])
    one = a[:CB // 2].copy()
    if mode == "codec":
        return [(n, dict(arr=x.astype(np.float32) - 128, codec="int8"), None)
                for n, x in (("a", a), ("b", b), ("one", one))]
    if mode == "full":
        return [(n, dict(arr=x), None)
                for n, x in (("a", a), ("b", b), ("one", one))]
    # dense xor: unchanged chunks elide, equal changes share a blob
    out = []
    for n, x in (("a", a), ("b", b), ("one", one)):
        prev = x.copy()
        prev[::3 * CB] ^= 1          # chunks 0, 3, 6, 9 change
        prev[CB + 5::3 * CB] ^= 1    # ... and 1, 4, 7
        out.append((n, dict(arr=x, prev=prev), None))
    return out


def _encode(mode, leaves, pool):
    """-> (manifest JSON, blobs, put order, patched mirrors, pool chunks
    per leaf)."""
    blobs, puts, mirrors, per_leaf = {}, [], {}, {}

    def put(h, d):
        assert h not in blobs
        puts.append(h)
        blobs[h] = d

    metas = {}
    for name, kw, _ in leaves:
        before = pool.counts["encode_pool_chunks"] if pool else 0
        if mode == "sparse":
            kw = dict(kw, prev=kw["prev"].copy())
            metas[name] = deltamod.encode_leaf_sparse(
                put_blob=put, has_blob=blobs.__contains__, pool=pool, **kw)
            mirrors[name] = kw["prev"]
        else:
            metas[name] = deltamod.encode_leaf(
                put_blob=put, has_blob=blobs.__contains__, pool=pool, **kw)
        per_leaf[name] = (pool.counts["encode_pool_chunks"] if pool else 0) \
            - before
    return json.dumps(metas, sort_keys=True), blobs, puts, mirrors, per_leaf


@pytest.mark.parametrize("mode", ["full", "xor", "codec", "sparse"])
def test_pooled_encode_is_byte_identical_to_serial(mode, executor,
                                                   monkeypatch):
    """Same manifests (with ``bytes_written``/``bytes_encoded``), the same
    blobs with the same bytes put in the same order, the same patched
    mirror; a one-chunk leaf never touches the pool."""
    leaves = _leaves(mode, np.random.RandomState(7))
    pool = deltamod.ChunkPool(executor, WINDOW)
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    meta_s, blobs_s, puts_s, mirror_s, _ = _encode(mode, leaves, None)
    meta_p, blobs_p, puts_p, mirror_p, per_leaf = _encode(mode, leaves, pool)
    assert meta_p == meta_s
    assert puts_p == puts_s and blobs_p == blobs_s
    for name in mirror_s:
        np.testing.assert_array_equal(mirror_p[name], mirror_s[name])
    metas = json.loads(meta_p)
    # the writer's dedupe was exercised: more chunk references than blobs
    refs = [h for m in metas.values() for h in deltamod.leaf_blob_names(m)]
    assert len(set(refs)) == len(blobs_p) < len(refs)
    assert any(e == deltamod.ENC_ZLIB for m in metas.values()
               for p in m["parts"].values()
               for e in (p.get("enc") or [d[2] for d in p["dirty"]]))
    assert per_leaf["one"] == 0 and per_leaf["a"] > WINDOW
    assert pool.counts["encode_pool_chunks"] == sum(per_leaf.values())
    assert pool.counts["encode_pool_cpu_seconds"] > 0
    if mode == "codec":
        return
    # what the pooled encode stored decodes to the leaf
    for name, kw, cur in leaves:
        got = deltamod.decode_leaf(metas[name], blobs_p.__getitem__,
                                   prev=kw.get("prev"))
        np.testing.assert_array_equal(
            got, cur if mode == "sparse" else kw["arr"])


def test_window_bounds_pending_results(executor, monkeypatch):
    """While ``put_blob`` blocks, the pool runs no more than the window's
    count of transforms ahead of what the encode thread has stored."""
    lock = threading.Lock()
    started, entered, ahead = [0], [0], []
    real = deltamod.transform_chunk

    def counting(data, compress):
        with lock:
            started[0] += 1
            ahead.append(started[0] - entered[0])
            first = started[0] == 1
        if first:
            time.sleep(0.3)        # the window fills behind the oldest
        return real(data, compress)

    monkeypatch.setattr(deltamod, "transform_chunk", counting)
    monkeypatch.setattr(deltamod, "CHUNK_BYTES", CB)
    gate = threading.Event()
    blobs = {}

    def put(h, d):
        with lock:
            entered[0] += 1
        gate.wait(timeout=60)
        blobs[h] = d

    n_chunks = 10
    arr = np.random.RandomState(1).randint(0, 256, n_chunks * CB, np.uint8)
    pool = deltamod.ChunkPool(executor, WINDOW)
    box = {}
    t = threading.Thread(target=lambda: box.setdefault(
        "meta", deltamod.encode_leaf(arr, put, blobs.__contains__,
                                     pool=pool)))
    t.start()
    try:
        deadline = time.monotonic() + 30
        while not entered[0] and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.3)            # room for the pool to overrun
        assert entered[0] == 1 and started[0] == WINDOW
    finally:
        gate.set()
        t.join(timeout=60)
    assert not t.is_alive()
    assert max(ahead) <= WINDOW
    assert started[0] == pool.counts["encode_pool_chunks"] == n_chunks
    assert pool.counts["encode_pool_wait_seconds"] > 0
    serial = deltamod.encode_leaf(arr, lambda h, d: None, lambda h: False)
    assert box["meta"] == serial


def _upper(w):
    up = UpperHalf()
    up.register("params", "params", {"w": w})
    return up


def test_failing_transform_fails_the_snapshot(tmp_path, monkeypatch):
    """A transform that raises on a chunk pool worker fails that save:
    its handle raises, ``drain()`` re-raises, the next save is a full
    base, and ``close()`` returns."""
    real = deltamod.transform_chunk
    armed = threading.Event()

    def flaky(data, compress):
        if armed.is_set() and \
                threading.current_thread().name.startswith("snap-chunk"):
            raise OSError("injected transform failure")
        return real(data, compress)

    monkeypatch.setattr(deltamod, "transform_chunk", flaky)
    cb = deltamod.CHUNK_BYTES
    snap = AsyncSnapshotter(LocalFSBackend(str(tmp_path)),
                            delta_base_interval=3)
    rng = np.random.RandomState(3)
    w = rng.randint(0, 256, 3 * cb, np.uint8)
    h1 = snap.snapshot(1, _upper(w), OpLog())
    assert h1.result(timeout=60)["base_step"] is None
    assert snap.stats["encode_pool_chunks"] >= 3

    w2 = w.copy()
    w2[::cb // 8] ^= 1                     # a link with many dirty chunks
    armed.set()
    h2 = snap.snapshot(2, _upper(w2), OpLog())
    with pytest.raises(OSError, match="injected"):
        h2.result(timeout=60)
    with pytest.raises(OSError, match="injected"):
        snap.drain()
    armed.clear()

    h3 = snap.snapshot(3, _upper(w2), OpLog())
    m3 = h3.result(timeout=60)
    assert m3["base_step"] is None        # chain broken: a full base
    assert m3["entries"]["params"]["leaves"]["['w']"]["mode"] == "full"
    assert snap.stats["failed"] == 1 and snap.stats["saves"] == 2

    closer = threading.Thread(target=snap.close)
    closer.start()
    closer.join(timeout=60)
    assert not closer.is_alive()


def test_pipeline_counts_pool_work(tmp_path):
    """A multi-chunk save runs its transforms on the chunk pool, which
    the snapshotter sizes from the host; the encode thread's own CPU
    time stays inside its encode, and the save restores bit for bit."""
    cb = deltamod.CHUNK_BYTES
    snap = AsyncSnapshotter(LocalFSBackend(str(tmp_path)))
    assert snap._chunk_workers == max(1, min(8, (os.cpu_count() or 1) // 2))
    w = np.random.RandomState(4).randint(0, 256, 5 * cb // 2, np.uint8)
    snap.snapshot(1, _upper(w), OpLog()).result(timeout=60)
    snap.close()
    st = snap.stats
    assert st["encode_pool_chunks"] == 3
    assert st["encode_pool_cpu_seconds"] > 0
    assert 0 <= st["encode_pool_wait_seconds"] \
        <= st["encode_commit_seconds"] - st["commit_seconds"]
    _, entries = materialize_manifest_chain(snap.backend, 1)
    np.testing.assert_array_equal(entries["params"]["['w']"], w)

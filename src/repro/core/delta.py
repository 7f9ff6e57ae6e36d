"""Delta codec: chunked, content-addressed, optionally chained snapshot
payloads (the record-prune-replay idea of paper §VI applied to bytes).

Three leaf encodings, chosen per tensor by the snapshot pipeline:

``full``   raw bytes, split into fixed-size chunks, each stored under its
           blake2b hash. Unchanged data (frozen embeddings, stale
           optimizer slots) re-uses existing blobs for free.
``codec``  lossy int8 block quantization (kernels/ckpt_codec — Pallas on
           TPU, numpy ref on host) applied before chunking; used for
           error-tolerant entries (optimizer moments), ~4x smaller.
``xor``    byte-level XOR against the *previous snapshot's* copy of the
           same leaf (through the ckpt_codec Pallas kernel when an
           accelerator is attached, numpy on host), forming a delta
           chain back to a full base snapshot.
           All-zero chunks (unchanged regions) are elided entirely, and
           non-zero chunks are zlib-compressed when that shrinks them, so
           the marginal cost of a snapshot is proportional to the entropy
           of what actually changed.

The encode API is *streaming*: ``encode_leaf`` walks a tensor one chunk
at a time (no whole-tensor XOR materialization) and hands each chunk to a
``put_blob`` callable, which the async snapshot pipeline backs with a
writer thread pool. Given a ``ChunkPool``, each chunk's store transform
(zlib, blake2b) runs on worker threads, at most the pool's window ahead
of ``put_blob``; blobs and metas are those of the serial encode.
``decode_leaf`` inverts one link, given a ``DecodePool`` with each
chunk's read, inflate and XOR on worker threads; chain walking lives in
``core.async_snapshot.materialize_manifest_chain``.

Manifest leaf format (format 2) — format-1 metas (no "mode" key) are
still decoded for old checkpoints:

    {"shape": [...], "dtype": "f32", "mode": "full|codec|xor",
     "codec": "int8"|None,
     "parts": {part: {"dtype", "shape", "chunks": [hash|None, ...],
                      "enc": ["raw"|"zlib", ...]}}}

Format 3 adds *sparse* xor parts, produced by the dirty-chunk capture
path (``encode_leaf_sparse``): instead of a dense chunk list with None
placeholders, the part records only the chunks that changed —

    {"dtype", "shape", "chunk_bytes": int, "n_chunks": int,
     "dirty": [[chunk_idx, hash, enc], ...]}

Decoding a sparse part XOR-patches the dirty chunks of (a copy of) the
base value, so chain application cost also scales with the delta. Formats
1-3 are all decoded by this module (compatibility matrix in README).
"""
from __future__ import annotations

import hashlib
import time
import zlib
from collections import deque
from concurrent.futures import FIRST_EXCEPTION, Executor, Future
from concurrent.futures import wait as futures_wait
from typing import (Any, Callable, Deque, Dict, Iterable, Iterator, List,
                    Optional, Tuple)

import numpy as np

from repro.api.errors import RestoreError, SnapshotError
from repro.spans import add, span

try:  # bfloat16 numpy interop (ships with jax)
    import ml_dtypes
    _BF16 = np.dtype(ml_dtypes.bfloat16)
except Exception:  # pragma: no cover
    ml_dtypes = None
    _BF16 = None

CHUNK_BYTES = 4 * 1024 * 1024

# chunk-level storage encodings
ENC_RAW = "raw"
ENC_ZLIB = "zlib"
# zlib level 1: ~GB/s on mostly-zero XOR streams, which is the case that
# matters; random float chunks fail the "did it shrink" test and stay raw
_ZLIB_LEVEL = 1


def _hash(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=20).hexdigest()


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        if _BF16 is None:
            raise SnapshotError("ml_dtypes unavailable for bfloat16")
        return _BF16
    return np.dtype(name)


# ---------------------------------------------------------------------------
# codecs (lossy, pre-chunking)
# ---------------------------------------------------------------------------

def _int8_encode(arr: np.ndarray) -> Dict[str, np.ndarray]:
    from repro.kernels.ckpt_codec.ref import quantize_ref
    q, scale = quantize_ref(np.asarray(arr, np.float32))
    return {"q": q, "scale": scale}


def _int8_decode(parts: Dict[str, np.ndarray], dtype: np.dtype,
                 shape: Tuple[int, ...]) -> np.ndarray:
    from repro.kernels.ckpt_codec.ref import dequantize_ref
    out = dequantize_ref(parts["q"], parts["scale"])
    return np.asarray(out[:int(np.prod(shape))].reshape(shape), dtype)


CODECS: Dict[str, Tuple[Callable, Callable]] = {
    "int8": (_int8_encode, _int8_decode),
}


def codec_applicable(arr: np.ndarray, codec: Optional[str]) -> bool:
    return (codec is not None and arr.dtype.kind == "f" and arr.size >= 256)


# ---------------------------------------------------------------------------
# streaming chunk encode/decode
# ---------------------------------------------------------------------------

def iter_chunk_views(arr: np.ndarray) -> Iterator[memoryview]:
    """Yield CHUNK_BYTES-sized byte views of a tensor without copying the
    whole thing (one contiguous materialization at most)."""
    flat = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    n = flat.nbytes
    if n == 0:
        yield memoryview(b"")
        return
    mv = memoryview(flat)
    for off in range(0, n, CHUNK_BYTES):
        yield mv[off:off + CHUNK_BYTES]


_PROBE_BYTES = 64 * 1024


def transform_chunk(data, compress: bool) -> Tuple[str, str, bytes]:
    """The store transform of one chunk: (hash, enc, payload). ``data``
    is any contiguous byte buffer (a chunk view, an XOR delta row)."""
    chunk = data.tobytes()
    enc = ENC_RAW
    if compress and len(chunk) > 0:
        # probe a prefix first: full-chunk zlib on incompressible float
        # noise costs real encode-thread CPU for nothing, and snapshot
        # payloads are bimodal (sparse XOR deltas ~ all compressible,
        # fresh random weights ~ not at all)
        probe = chunk[:_PROBE_BYTES]
        if len(zlib.compress(probe, _ZLIB_LEVEL)) < len(probe) * 9 // 10:
            packed = zlib.compress(chunk, _ZLIB_LEVEL)
            if len(packed) < len(chunk) * 9 // 10:
                chunk, enc = packed, ENC_ZLIB
    return _hash(chunk), enc, chunk


# a part of fewer chunks than this transforms (or decodes) them on the
# calling thread: a worker handoff buys nothing without a second chunk
# to overlap
POOL_MIN_CHUNKS = 2


class ChunkPool:
    """One save's chunk transforms on a shared worker pool. zlib and
    blake2b release the GIL on large buffers, so the transforms of
    consecutive chunks run in parallel while the encode thread keeps
    the XOR, the blob-store calls and the manifest, in chunk order.
    At most ``window`` results are pending at a time.

    ``counts``: ``encode_pool_chunks`` (transforms submitted),
    ``encode_pool_cpu_seconds`` (the workers' ``time.thread_time()``
    over them) and ``encode_pool_wait_seconds`` (the encode thread
    blocked on the oldest result)."""

    def __init__(self, executor: Executor, window: int) -> None:
        self._executor = executor
        self.window = window
        self.counts = {"encode_pool_chunks": 0,
                       "encode_pool_cpu_seconds": 0.0,
                       "encode_pool_wait_seconds": 0.0}

    def submit(self, data, compress: bool) -> Future:
        add(self.counts, "encode_pool_chunks", 1)
        return self._executor.submit(self._run, data, compress)

    def _run(self, data, compress: bool) -> Tuple[str, str, bytes]:
        cpu0 = time.thread_time()
        try:
            with span("ckpt.encode.chunk", bytes=data.nbytes):
                return transform_chunk(data, compress)
        finally:
            add(self.counts, "encode_pool_cpu_seconds",
                time.thread_time() - cpu0)

    def result(self, fut: Future) -> Tuple[str, str, bytes]:
        if fut.done():
            return fut.result()
        t0 = time.perf_counter()
        try:
            return fut.result()
        finally:
            add(self.counts, "encode_pool_wait_seconds",
                time.perf_counter() - t0)


def _store_chunks(datas: Iterable[Optional[Any]], put_blob, has_blob,
                  compress: bool, pool: Optional[ChunkPool] = None,
                  ) -> Tuple[List[Optional[Tuple[str, str]]], int]:
    """Transform and store chunks; a None (an elided all-zero chunk)
    stays None. Returns ([(hash, enc) | None, ...], bytes_written).

    ``has_blob``/``put_blob`` run on the calling thread in chunk order
    either way, so blobs, dedupe and the returned list are the same with
    and without ``pool``; with it the transforms run ahead on its
    workers, as far as its window."""
    out: List[Optional[Tuple[str, str]]] = []
    written = 0

    def store(t: Optional[Tuple[str, str, bytes]]) -> None:
        nonlocal written
        if t is None:
            out.append(None)
            return
        h, enc, payload = t
        if not has_blob(h):
            put_blob(h, payload)
            written += len(payload)
        out.append((h, enc))

    if pool is None:
        for d in datas:
            store(None if d is None else transform_chunk(d, compress))
        return out, written
    pending: Deque[Optional[Future]] = deque()
    try:
        for d in datas:
            while pending and (len(pending) >= pool.window
                               or pending[0] is None or pending[0].done()):
                f = pending.popleft()
                store(None if f is None else pool.result(f))
            pending.append(None if d is None else pool.submit(d, compress))
        while pending:
            f = pending.popleft()
            store(None if f is None else pool.result(f))
    finally:
        # after a failure: no transform outlives the save that owns its
        # buffer
        left = [f for f in pending if f is not None and not f.cancel()]
        futures_wait(left)
    return out, written


def _flat_bytes(a: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def _decode_chunk(get_blob, entry: Optional[str], enc: str,
                  dst: np.ndarray, against: Optional[np.ndarray]) -> None:
    """Decode one stored chunk into ``dst``, its slot of the part's
    output: the chunk's bytes, or those XORed with ``against`` (the base
    value's slice, or ``dst`` itself when it already holds the base)."""
    if entry is None:  # elided all-zero chunk
        if against is None:
            dst[:] = 0
        elif against is not dst:
            dst[:] = against
        return
    data = get_blob(entry)
    if enc == ENC_ZLIB:
        data = zlib.decompress(data)
    if len(data) != dst.nbytes:
        raise RestoreError(f"chunk {entry} decodes to {len(data)} bytes; "
                           f"its slot holds {dst.nbytes}")
    buf = np.frombuffer(data, np.uint8)
    if against is None:
        dst[:] = buf
    else:
        np.bitwise_xor(buf, against, out=dst)


class DecodePool:
    """One leaf's chunk decodes on a shared worker pool. The blob reads,
    zlib and numpy's copy and XOR release the GIL, so the chunks of a
    large part decode in parallel; each writes its own slice of the
    part's output, so they need no lock. The leaf's thread only submits
    a part's chunks, waits for them and walks on to the next link.

    ``counts``: ``decode_pool_chunks`` (chunk tasks run),
    ``decode_pool_wait_s`` (the leaf's thread blocked on them) and
    ``decode_pool_task_s`` (the tasks' own seconds, reads included)."""

    def __init__(self, executor: Executor) -> None:
        self._executor = executor
        self.counts = {"decode_pool_chunks": 0, "decode_pool_wait_s": 0.0,
                       "decode_pool_task_s": 0.0}

    def _run(self, get_blob, entry, enc, dst, against) -> None:
        with span("ckpt.restore.chunk", bytes=dst.nbytes) as sp:
            _decode_chunk(get_blob, entry, enc, dst, against)
        add(self.counts, "decode_pool_task_s", sp.seconds)

    def run(self, get_blob, jobs: List[Tuple]) -> None:
        """Decode every ``(entry, enc, dst, against)`` job on the pool and
        return when all are done. A failure is raised once every other
        job has been cancelled or has finished: none outlives the call."""
        futs: List[Future] = []
        try:
            for job in jobs:
                futs.append(self._executor.submit(self._run, get_blob, *job))
            add(self.counts, "decode_pool_chunks", len(futs))
            t0 = time.perf_counter()
            futures_wait(futs, return_when=FIRST_EXCEPTION)
            add(self.counts, "decode_pool_wait_s", time.perf_counter() - t0)
            for f in futs:
                if f.done() and f.exception() is not None:
                    f.result()
        finally:
            futures_wait([f for f in futs if not f.cancel()])


def _decode_chunks(jobs: List[Tuple], get_blob,
                   pool: Optional[DecodePool]) -> None:
    if pool is not None and len(jobs) >= POOL_MIN_CHUNKS:
        pool.run(get_blob, jobs)
    else:
        for job in jobs:
            _decode_chunk(get_blob, *job)


_DEVICE_XOR_MIN_BYTES = 1 << 20
_device_xor: Optional[bool] = None


def _use_device_xor() -> bool:
    """XOR through the Pallas kernel when a TPU is attached
    (kernels/ckpt_codec), numpy otherwise. A failed backend probe
    raises: it must not pass for "no device"."""
    global _device_xor
    if _device_xor is None:
        import jax
        _device_xor = jax.default_backend() == "tpu"
    return _device_xor


def _encode_part(p: np.ndarray, put_blob, has_blob, *,
                 prev: Optional[np.ndarray] = None,
                 compress: bool = True,
                 pool: Optional[ChunkPool] = None,
                 ) -> Tuple[Dict[str, Any], int]:
    """Chunk one part array; XOR against `prev` chunk-by-chunk when given
    (streaming — never materializes the full delta)."""
    # the device-vs-host XOR decision is per-part, not per-chunk: the
    # backend probe is hoisted out of the chunk loop
    if prev is not None and _use_device_xor():
        from repro.kernels.ckpt_codec import ops

        def xor(a, b):
            if a.nbytes >= _DEVICE_XOR_MIN_BYTES:
                return ops.delta_encode(a, b)
            return np.bitwise_xor(a, b)
    else:
        xor = np.bitwise_xor

    def datas() -> Iterator[Optional[Any]]:
        if prev is None:
            yield from iter_chunk_views(p)
            return
        for view, pview in zip(iter_chunk_views(p), iter_chunk_views(prev)):
            delta = xor(np.frombuffer(view, np.uint8),
                        np.frombuffer(pview, np.uint8))
            # an unchanged region costs nothing
            yield delta if delta.any() else None

    if -(-p.nbytes // CHUNK_BYTES) < POOL_MIN_CHUNKS:
        pool = None
    stored, written = _store_chunks(datas(), put_blob, has_blob, compress,
                                    pool)
    meta = {"dtype": str(p.dtype), "shape": list(p.shape),
            "chunks": [None if s is None else s[0] for s in stored],
            "enc": [ENC_RAW if s is None else s[1] for s in stored]}
    return meta, written


def _decode_part(pmeta: Dict[str, Any], get_blob,
                 prev: Optional[np.ndarray] = None,
                 pool: Optional[DecodePool] = None,
                 patch_prev: bool = False) -> np.ndarray:
    if "dirty" in pmeta:  # format-3 sparse dirty-chunk part
        if prev is None:
            raise RestoreError("sparse xor part needs its base-step value")
        return _decode_part_sparse(pmeta, get_blob, prev, pool, patch_prev)
    dt = _np_dtype(pmeta["dtype"])
    shape = pmeta["shape"]
    total = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    chunks = pmeta["chunks"]
    encs = pmeta.get("enc") or [ENC_RAW] * len(chunks)
    if len(chunks) != max(1, -(-total // CHUNK_BYTES)):
        raise RestoreError(f"a part of {total} bytes lists {len(chunks)} "
                           f"chunks of {CHUNK_BYTES}")
    base = None if prev is None else _flat_bytes(prev)
    if base is not None and base.size != total:
        raise RestoreError(f"xor base holds {base.size} bytes, the part "
                           f"{total}")
    out = base if patch_prev and base is not None \
        else np.empty(total, np.uint8)
    jobs = []
    for i, (entry, enc) in enumerate(zip(chunks, encs)):
        sl = slice(i * CHUNK_BYTES, (i + 1) * CHUNK_BYTES)
        dst = out[sl]
        jobs.append((entry, enc, dst,
                     None if base is None else dst if out is base
                     else base[sl]))
    _decode_chunks(jobs, get_blob, pool)
    return out.view(dt).reshape(shape)


# ---------------------------------------------------------------------------
# sparse (dirty-chunk) encode/decode — manifest format 3 leaves
# ---------------------------------------------------------------------------

def encode_leaf_sparse(
    shape: Tuple[int, ...],
    dtype: np.dtype,
    chunk_bytes: int,
    n_chunks: int,
    dirty_idx: np.ndarray,
    dirty_bytes: np.ndarray,
    prev: np.ndarray,
    put_blob: Callable[[str, bytes], None],
    has_blob: Callable[[str], bool],
    *,
    compress: bool = True,
    patch_prev: bool = True,
    pool: Optional[ChunkPool] = None,
) -> Dict[str, Any]:
    """Encode one leaf from a sparse dirty-chunk capture.

    ``dirty_bytes`` is the gather-compacted [k, chunk_bytes] uint8 payload
    from capture (tail chunk zero-padded); ``prev`` is the previous
    snapshot's full value of this leaf (the XOR base). Only the dirty
    chunks are XORed, hashed and stored — encode work scales with what
    changed, not with the leaf.

    When ``patch_prev`` (the pipeline's mode), ``prev`` is updated IN
    PLACE chunk by chunk, so after the leaf is encoded the buffer holds
    the *current* snapshot's bytes — the pipeline keeps one full host
    mirror alive instead of two.

    ``pool`` transforms the dirty chunks on its workers when there are
    at least ``POOL_MIN_CHUNKS`` of them; the blobs and the meta are the
    same as without it.
    """
    dtype = np.dtype(dtype)
    total = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    prev_b = np.ascontiguousarray(prev).reshape(-1).view(np.uint8)
    assert prev_b.size == total, (prev_b.size, total)
    encoded = 0
    idxs = np.asarray(dirty_idx, np.int64)
    if idxs.size and np.any(np.diff(idxs) < 0):  # capture emits sorted;
        order = np.argsort(idxs)                 # guard other callers
        idxs = idxs[order]
        dirty_bytes = dirty_bytes[order]
    # one vectorized XOR + fancy-index patch over every full dirty chunk
    # (the hot path: k SIMD row ops instead of a k-iteration Python
    # loop); only a partial tail chunk — at most one, and only when the
    # leaf isn't a chunk multiple — takes the scalar path below. idxs
    # arrive in ascending chunk order from capture, so the tail (the
    # largest index) is last and the manifest order is unchanged.
    n_full = total // chunk_bytes
    k_full = int(np.searchsorted(idxs, n_full))
    todo: List[Tuple[int, np.ndarray]] = []   # (chunk index, XOR delta)
    if k_full:
        grid = prev_b[:n_full * chunk_bytes].reshape(n_full, chunk_bytes)
        fi = idxs[:k_full]
        cur_rows = dirty_bytes[:k_full]
        deltas = np.bitwise_xor(cur_rows, grid[fi])
        changed = deltas.any(axis=1)
        if patch_prev:
            grid[fi] = cur_rows  # in-place mirror advance, one scatter
        encoded += k_full * chunk_bytes
        todo += [(int(idxs[j]), deltas[j]) for j in np.nonzero(changed)[0]]
    for j in range(k_full, idxs.size):  # partial tail chunk
        off = int(idxs[j]) * chunk_bytes
        ln = total - off
        cur = dirty_bytes[j, :ln]
        pv = prev_b[off:off + ln]
        delta = np.bitwise_xor(cur, pv)
        encoded += ln
        if patch_prev:
            pv[:] = cur
        if delta.any():  # else a conservative dirty mark: nothing changed
            todo.append((int(idxs[j]), delta))
    stored, written = _store_chunks(
        (d for _, d in todo), put_blob, has_blob, compress,
        pool if len(todo) >= POOL_MIN_CHUNKS else None)
    dirty = [[i, h, enc] for (i, _), (h, enc) in zip(todo, stored)]
    return {
        "shape": list(shape),
        "dtype": str(dtype),
        "codec": None,
        "mode": "xor",
        "parts": {"raw": {"dtype": str(dtype), "shape": list(shape),
                          "chunk_bytes": int(chunk_bytes),
                          "n_chunks": int(n_chunks),
                          "dirty": dirty}},
        "bytes_written": written,
        "bytes_encoded": encoded,
    }


def _decode_part_sparse(pmeta: Dict[str, Any], get_blob,
                        prev: np.ndarray,
                        pool: Optional[DecodePool] = None,
                        patch_prev: bool = False) -> np.ndarray:
    """Sparse chain link: XOR-patch only the dirty chunks of the base
    value (a copy of it, or ``prev`` itself with ``patch_prev``), so
    chain application cost scales with the delta."""
    dt = _np_dtype(pmeta["dtype"])
    shape = pmeta["shape"]
    cb = pmeta["chunk_bytes"]
    total = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
    out = _flat_bytes(prev)
    if out.size != total:
        raise RestoreError(f"xor base holds {out.size} bytes, the part "
                           f"{total}")
    if not patch_prev:
        out = out.copy()
    n_chunks = -(-total // cb)
    jobs = []
    last = -1
    for idx, entry, enc in pmeta["dirty"]:
        # ascending and in range, so no two jobs write one slice
        if not last < idx < n_chunks:
            raise RestoreError(f"sparse part: dirty chunk {idx} after "
                               f"{last}, of {n_chunks}")
        last = idx
        dst = out[idx * cb:(idx + 1) * cb]
        jobs.append((entry, enc, dst, dst))
    _decode_chunks(jobs, get_blob, pool)
    return out.view(dt).reshape(shape)


# ---------------------------------------------------------------------------
# leaf encode/decode (one tensor, one chain link)
# ---------------------------------------------------------------------------

def encode_leaf(
    arr: np.ndarray,
    put_blob: Callable[[str, bytes], None],
    has_blob: Callable[[str], bool],
    *,
    codec: Optional[str] = None,
    prev: Optional[np.ndarray] = None,
    compress: bool = True,
    pool: Optional[ChunkPool] = None,
) -> Dict[str, Any]:
    """Encode one tensor into blobs + leaf manifest.

    ``prev`` (same shape/dtype tensor from the previous snapshot) selects
    xor mode; ``codec`` selects the lossy codec (mutually exclusive with
    xor — quantized entries rely on chunk dedup instead, so requantization
    noise never accumulates along a chain). ``pool`` transforms the
    chunks of each part of at least ``POOL_MIN_CHUNKS`` chunks on its
    workers; the blobs and the meta are the same as without it."""
    arr = np.asarray(arr)
    meta: Dict[str, Any] = {
        "shape": list(arr.shape),
        "dtype": str(arr.dtype),
        "codec": None,
        "parts": {},
    }
    written = 0
    if codec_applicable(arr, codec):
        meta["mode"] = "codec"
        meta["codec"] = codec
        for pname, p in CODECS[codec][0](arr).items():
            pmeta, w = _encode_part(p, put_blob, has_blob, compress=compress,
                                    pool=pool)
            meta["parts"][pname] = pmeta
            written += w
    elif (prev is not None and prev.shape == arr.shape
          and prev.dtype == arr.dtype):
        meta["mode"] = "xor"
        pmeta, w = _encode_part(arr, put_blob, has_blob, prev=prev,
                                compress=compress, pool=pool)
        meta["parts"]["raw"] = pmeta
        written += w
    else:
        meta["mode"] = "full"
        pmeta, w = _encode_part(arr, put_blob, has_blob, compress=compress,
                                pool=pool)
        meta["parts"]["raw"] = pmeta
        written += w
    meta["bytes_written"] = written
    # dense modes read + process the whole leaf regardless of how little
    # changed; the sparse encoder reports only its dirty-chunk bytes here
    meta["bytes_encoded"] = arr.nbytes
    return meta


def decode_leaf(meta: Dict[str, Any],
                get_blob: Callable[[str], bytes],
                prev: Optional[np.ndarray] = None, *,
                pool: Optional[DecodePool] = None,
                patch_prev: bool = False) -> np.ndarray:
    """Decode one leaf. xor-mode leaves need ``prev`` — the decoded value
    of the same leaf at the manifest's base step. ``pool`` decodes the
    chunks of each part of at least ``POOL_MIN_CHUNKS`` chunks (dirty
    chunks, for a sparse part) on its workers; the array is the same as
    without it. ``patch_prev``: an xor leaf writes its value into
    ``prev``'s buffer instead of a new one, for a caller that owns
    ``prev`` and drops it."""
    dtype = _np_dtype(meta["dtype"])
    shape = tuple(meta["shape"])
    mode = meta.get("mode")
    if mode is None:  # format-1 manifest
        mode = "codec" if meta.get("codec") else "full"
    if mode == "xor":
        if prev is None:
            raise RestoreError("xor leaf needs its base-step value")
        return _decode_part(meta["parts"]["raw"], get_blob, prev=prev,
                            pool=pool, patch_prev=patch_prev).reshape(shape)
    parts = {pname: _decode_part(pmeta, get_blob, pool=pool)
             for pname, pmeta in meta["parts"].items()}
    if mode == "codec":
        return CODECS[meta["codec"]][1](parts, dtype, shape)
    return np.asarray(parts["raw"], dtype).reshape(shape)


# ---------------------------------------------------------------------------
# format-1 compatibility shims (whole-tree, no chaining)
# ---------------------------------------------------------------------------

def serialize_tensor(
    arr: np.ndarray,
    put_blob: Callable[[str, bytes], None],
    has_blob: Callable[[str], bool],
    codec: Optional[str] = None,
) -> Dict[str, Any]:
    """Chunk + store a tensor (full/codec only). Kept for callers that
    predate the chained API; equivalent to ``encode_leaf`` without
    ``prev``."""
    return encode_leaf(arr, put_blob, has_blob, codec=codec)


def deserialize_tensor(meta: Dict[str, Any],
                       get_blob: Callable[[str], bytes],
                       prev: Optional[np.ndarray] = None) -> np.ndarray:
    return decode_leaf(meta, get_blob, prev=prev)


def leaf_blob_names(meta: Dict[str, Any]) -> List[str]:
    """Every blob hash one leaf's manifest meta references, in decode
    order (elided zero chunks excluded)."""
    out: List[str] = []
    for pmeta in meta["parts"].values():
        if "dirty" in pmeta:
            out.extend(h for _, h, _ in pmeta["dirty"])
        else:
            out.extend(h for h in pmeta["chunks"] if h is not None)
    return out


def referenced_hashes(manifest: Dict[str, Any]) -> set:
    out = set()
    for entry in manifest.get("entries", {}).values():
        for leaf in entry["leaves"].values():
            out.update(leaf_blob_names(leaf))
    return out

"""Spans and counters of the checkpoint pipeline and the serving engine
(repro/spans.py): one measurement feeds both the profiler's trace and the
counter dicts the pipeline, its handles and the restore already keep."""
import glob
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CheckpointManager, Incarnation, LocalFSBackend,
                        OpLog, UpperHalf)
from repro.spans import add, span
from repro.kernels.ckpt_codec.ref import FP_CHUNK_BYTES as CB

# stats keys the pipeline kept before it was spanned; each must survive
OLD_STATS = {"saves", "skipped", "failed", "bytes_written", "bytes_logical",
             "bytes_encoded", "capture_bytes", "sparse_leaves",
             "identity_skips", "dirty_chunks", "clean_chunks",
             "fp_kernel_leaves", "fused_leaves", "fused_overflows",
             "host_fp_leaves", "capture_seconds", "encode_commit_seconds"}
NEW_STATS = {"d2h_bytes", "d2h_seconds", "slot_wait_seconds",
             "encode_cpu_seconds", "encode_put_wait_seconds",
             "commit_seconds", "write_seconds", "encode_pool_chunks",
             "encode_pool_cpu_seconds", "encode_pool_wait_seconds"}


def test_span_adds_its_seconds_into_the_callers_dict():
    into = {"other": 1}
    with span("ckpt.test", into=into, key="t") as sp:
        time.sleep(0.02)
    assert sp.seconds >= 0.02
    assert into == {"other": 1, "t": sp.seconds}
    with span("ckpt.test", into=into, key="t") as sp2:
        pass
    assert into["t"] == pytest.approx(sp.seconds + sp2.seconds)
    with span("ckpt.test") as sp3:      # no dict: only the annotation
        pass
    assert sp3.seconds >= 0


def test_spans_nest():
    into = {}
    with span("ckpt.outer", into=into, key="outer") as outer:
        with span("ckpt.inner", into=into, key="inner") as inner:
            time.sleep(0.01)
        time.sleep(0.01)
    assert into["outer"] >= into["inner"] + 0.01
    assert (outer.seconds, inner.seconds) == (into["outer"], into["inner"])


def _profile_spans(tmp_path, prefix):
    """Every span named ``prefix...`` of the newest profile under
    ``tmp_path``: (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith(prefix)), key=lambda x: x[1])


def test_args_reach_the_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("ckpt.outer", step=7):
            with span("ckpt.inner", path="kv:['k']", bytes=12):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    found = {name: (a, b, args)
             for name, a, b, args in _profile_spans(tmp_path, "ckpt.")}
    assert found["ckpt.outer"][2] == {"step": 7}
    assert found["ckpt.inner"][2] == {"path": "kv:['k']", "bytes": 12}
    # nested on the profile's clock
    assert found["ckpt.outer"][0] <= found["ckpt.inner"][0]
    assert found["ckpt.inner"][1] <= found["ckpt.outer"][1]


def test_concurrent_spans_sum_exactly():
    """Writer-pool threads add into one dict: no update may be lost."""
    into = {}
    n_threads, n_spans = 16, 300
    mine = [[] for _ in range(n_threads)]

    def work(k):
        for _ in range(n_spans):
            with span("ckpt.write", into=into, key="write_seconds") as sp:
                pass
            mine[k].append(sp.seconds)
            add(into, "n", 1)

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert into["n"] == n_threads * n_spans
    assert into["write_seconds"] == pytest.approx(
        sum(sum(m) for m in mine), rel=1e-9)


def test_chained_save_and_restore_keep_every_counter(tmp_path):
    """A base and a dirty-chunk link, then a restore: the pipeline's
    stats, each handle's timings and the incarnation's timings hold every
    key they had plus the new ones, and ``d2h_bytes`` is exactly what the
    captures copied off the device."""
    n = 8 * CB // 4
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    b = jnp.asarray(rng.randn(n).astype(np.float32))   # same object: skip
    s = jnp.asarray(rng.randn(16).astype(np.float32))  # small: dense
    h = rng.randn(n).astype(np.float32)                # host leaf
    mgr = CheckpointManager(LocalFSBackend(str(tmp_path)),
                            delta_base_interval=2, sparse_chunk_bytes=CB)
    up = UpperHalf()
    up.register("params", "params", {"w": w, "b": b, "s": s, "h": h})
    h0 = mgr.save(1, up, OpLog())
    w2 = w.at[5].add(1.0)
    up.register("params", "params", {"w": w2, "b": b, "s": s, "h": h})
    h1 = mgr.save(2, up, OpLog())
    mgr.wait()
    st = mgr.stats
    assert OLD_STATS | NEW_STATS <= set(st)
    assert not {"save_seconds", "chain_links"} & set(st)
    assert all(st[k] >= 0 for k in OLD_STATS | NEW_STATS)
    assert st["saves"] == 2 and st["sparse_leaves"] >= 1
    # base: every device leaf whole; link: the changed leaf (host
    # fingerprint path reads it whole) and the small dense one; the
    # unchanged Array is skipped without a read; the host leaf never moves
    assert st["d2h_bytes"] == (w.nbytes + b.nbytes + s.nbytes) \
        + (w2.nbytes + s.nbytes)
    assert st["d2h_seconds"] > 0
    assert st["write_seconds"] > 0 and st["commit_seconds"] > 0
    # the encode's CPU time and write waits lie outside its commit
    assert 0 < st["encode_cpu_seconds"] \
        <= st["encode_commit_seconds"] - st["commit_seconds"] + 0.01
    assert st["encode_put_wait_seconds"] \
        <= st["encode_commit_seconds"] - st["commit_seconds"]
    for hd in (h0, h1):
        assert set(hd.timings) == {"capture", "encode_commit"}
        assert all(v >= 0 for v in hd.timings.values())

    inc = Incarnation(mgr, step=2)
    restored = inc.materialize()
    inc.build_lower()
    inc.bind("params", {"w": w, "b": b, "s": s, "h": h})
    t = inc.timings
    assert {"materialize_s", "replay_s", "rebind_s", "fetch_s",
            "decode_s"} <= set(t)
    assert all(t[k] >= 0 for k in t)
    assert t["fetch_s"] > 0 and t["decode_s"] > 0
    np.testing.assert_array_equal(restored.entries["params"]["['w']"],
                                  np.asarray(w2))
    mgr.close()


def test_engine_step_spans(tmp_path):
    """Each engine step is one ``serve.step`` span holding its blocking
    ``serve.sync``; an admission with a history is a ``serve.admit``
    carrying its request id."""
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("phi4-mini-3.8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    eng = ServingEngine(cfg, params, mesh, n_slots=2, max_seq=32)
    eng.submit(Request(rid=3, prompt=np.arange(4), max_new=3))
    eng.step()                          # compiles outside the profile
    eng.submit(Request(rid=4, prompt=np.arange(5), max_new=3))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = _profile_spans(tmp_path, "serve.")
    steps = [s for s in spans if s[0] == "serve.step"]
    syncs = [s for s in spans if s[0] == "serve.sync"]
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert len(steps) == len(syncs) == 2
    for (_, a, b, _), (_, c, d, _) in zip(steps, syncs):
        assert a <= c <= d <= b
    assert [s[3]["rid"] for s in admits] == [4]
    assert steps[0][1] <= admits[0][1] <= admits[0][2] <= steps[0][2]

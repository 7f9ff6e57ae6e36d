"""``encode_parallelism``: encode CPU seconds, the encode thread's and
the chunk pool's, per second of encode outside the commit."""
import math

import pytest

import harness as H

READER = H.load_module(H.BENCH / "metrics" / "encode_parallelism.py")

STATS = {"saves": 2, "bytes_written": 3e9, "bytes_logical": 4.3e9,
         "encode_commit_seconds": 12.0, "encode_cpu_seconds": 4.0,
         "encode_put_wait_seconds": 0.5, "commit_seconds": 2.0,
         "encode_pool_chunks": 1024, "encode_pool_cpu_seconds": 46.0,
         "encode_pool_wait_seconds": 1.5}


def test_reader_on_a_hand_built_run():
    assert READER.read({"stats": STATS}, None) == pytest.approx(
        (4.0 + 46.0) / (12.0 - 2.0))


@pytest.mark.parametrize("drop", ["encode_pool_cpu_seconds",
                                  "encode_cpu_seconds", "commit_seconds"])
def test_reader_finds_nothing_without_the_counters(drop):
    """A program without the chunk pool (or the encode spans) leaves the
    metric out, and does not raise."""
    st = {k: v for k, v in STATS.items() if k != drop}
    assert READER.read({"stats": st}, None) is None
    assert READER.read({}, None) is None
    assert READER.read({"stats": dict(STATS, saves=0)}, None) is None


def test_smoke_run_reads_it(smoke_ctx):
    """On a smoke run of the serve cell the metric is the cell's and
    finite, and the pool's counters are there to read."""
    ctx = smoke_ctx("serve-phi4-snap", seed=2 ** 33 + 11, seconds=2.0)
    run = ctx.cell.driver().serve(ctx)[0]["run"]
    assert "encode_parallelism" in {m["name"] for m in ctx.cell.per_layer()}
    got = READER.read(run, None)
    assert got is not None and math.isfinite(got) and got > 0
    st = run["stats"]
    assert st["encode_pool_chunks"] >= 0
    assert st["encode_pool_wait_seconds"] \
        <= st["encode_commit_seconds"] - st["commit_seconds"]

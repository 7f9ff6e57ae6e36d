"""``restore_parallelism``: the restore's reads and decoding, summed over
its threads, per second of ``materialize_s``."""
import math

import pytest

import harness as H

READER = H.load_module(H.BENCH / "metrics" / "restore_parallelism.py")

RESTORE = {"materialize_s": 4.0, "replay_s": 0.07, "fetch_s": 2.5,
           "decode_s": 15.5, "decode_pool_chunks": 4096.0,
           "decode_pool_wait_s": 7.5}


def test_reader_on_a_hand_built_run():
    assert READER.read({"restore": RESTORE}, None) == pytest.approx(
        (2.5 + 15.5) / 4.0)


@pytest.mark.parametrize("drop", ["decode_pool_chunks", "fetch_s",
                                  "decode_s", "materialize_s"])
def test_reader_finds_nothing_without_the_counters(drop):
    """A program without the restore's chunk pool (or its restore
    timings) leaves the metric out, and does not raise."""
    r = {k: v for k, v in RESTORE.items() if k != drop}
    assert READER.read({"restore": r}, None) is None
    assert READER.read({}, None) is None
    assert READER.read({"restore": dict(RESTORE, materialize_s=0.0)},
                       None) is None


def test_smoke_run_reads_it(smoke_ctx):
    """On a smoke run of the serve cell the metric is the cell's and
    finite, and the restore's chunk-pool counters are there to read."""
    ctx = smoke_ctx("serve-phi4-snap", seed=2 ** 33 + 17, seconds=2.0)
    run = ctx.cell.driver().serve(ctx)[0]["run"]
    assert "restore_parallelism" in {m["name"]
                                     for m in ctx.cell.per_layer()}
    got = READER.read(run, None)
    assert got is not None and math.isfinite(got) and got > 0
    r = run["restore"]
    assert r["decode_pool_chunks"] >= 0
    assert r["decode_pool_wait_s"] >= 0

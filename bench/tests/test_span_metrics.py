"""The per-layer metrics read from the program's span counters: each
reader on a hand-built run, on a run of a program without the counters
(nothing to read), and all of them on a smoke run of the serve cell."""
import math

import pytest

import harness as H


def _reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py")


STATS = {"saves": 2, "bytes_written": 3e9, "bytes_logical": 4.3e9,
         "encode_commit_seconds": 60.0, "encode_cpu_seconds": 18.0,
         "encode_put_wait_seconds": 15.0, "commit_seconds": 4.5,
         "write_seconds": 100.0, "slot_wait_seconds": 0.25,
         "d2h_bytes": 2.9e9, "d2h_seconds": 3.625}
RESTORE = {"materialize_s": 9.0, "replay_s": 0.02, "fetch_s": 6.5,
           "decode_s": 7.25}
COUNTER_METRICS = ["d2h_gb_per_s", "encode_cpu_s", "encode_offcpu_s",
                   "blob_write_mb_per_s", "restore_fetch_s",
                   "restore_decode_s", "slot_wait_s", "commit_s"]


@pytest.mark.parametrize("name,want", [
    ("d2h_gb_per_s", 2.9 / 3.625),
    ("encode_cpu_s", 18.0),
    ("encode_offcpu_s", 60.0 - 4.5 - 15.0 - 18.0),
    ("blob_write_mb_per_s", 3e9 / 1e6 / 100.0),
    ("restore_fetch_s", 6.5),
    ("restore_decode_s", 7.25),
    ("slot_wait_s", 0.25),
    ("commit_s", 4.5)])
def test_reader_on_a_hand_built_run(name, want):
    run = {"stats": STATS, "restore": RESTORE}
    assert _reader(name).read(run, None) == pytest.approx(want)


@pytest.mark.parametrize("name", COUNTER_METRICS)
def test_reader_finds_nothing_without_the_counters(name):
    """A program without the spans leaves the metric out, and does not
    raise: the stats and timings it kept before."""
    old = {k: v for k, v in STATS.items()
           if k in ("saves", "bytes_written", "bytes_logical",
                    "encode_commit_seconds")}
    run = {"stats": old, "restore": {"materialize_s": 9.0,
                                     "replay_s": 0.02}}
    assert _reader(name).read(run, None) is None
    assert _reader(name).read({}, None) is None


def test_step_host_ms_reads_nothing_without_engine_spans(tmp_path,
                                                        monkeypatch):
    """Untraced, with no kept trace, or on a trace of a program without
    the engine's spans, the span metric is left out."""
    import shutil
    reader = _reader("step_host_ms.serve")
    assert reader.read({"stats": STATS}, None) is None
    monkeypatch.setattr(reader.P, "TRACES", tmp_path)
    assert reader.read({"stats": STATS}, object()) is None
    shutil.copy(H.BENCH / "testdata" / "capture_kernels.xplane.pb",
                tmp_path / "t.xplane.pb")
    assert reader.read({"stats": STATS}, object()) is None


def test_smoke_run_reads_every_counter_metric(smoke_ctx):
    """Each metric read from the program's span counters is one of the
    serve cell's and finite on a real run; the encode's CPU and off-CPU
    seconds lie within its own."""
    ctx = smoke_ctx("serve-phi4-snap", seed=2 ** 33 + 5, seconds=2.0)
    run = ctx.cell.driver().serve(ctx)[0]["run"]
    names = {m["name"] for m in ctx.cell.per_layer()}
    assert "step_host_ms.serve" in names
    got = {}
    for n in COUNTER_METRICS:
        assert n in names
        got[n] = _reader(n).read(run, None)
        assert got[n] is not None and math.isfinite(got[n]), n
    assert got["d2h_gb_per_s"] > 0 and got["encode_cpu_s"] > 0
    st = run["stats"]
    assert got["encode_cpu_s"] + got["encode_offcpu_s"] + got["commit_s"] \
        <= st["encode_commit_seconds"] + 1e-9
    assert got["slot_wait_s"] >= 0

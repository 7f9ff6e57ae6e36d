"""Idle gaps labelled by the program's spans (``bench/program_spans.py``):
the labelling rule on hand-built spans; a trace recorded on a TPU v5e
by ``bench/testdata/record_serve_spans.py`` (four engine steps of the
phi4-mini smoke model with a dirty-chunk link saved at the last, waited
for, restored and stepped once); and a trace recorded before the
program had spans, which keeps the harness's labels."""
from pathlib import Path

import pytest

import harness as H

DATA = Path(__file__).resolve().parents[1] / "testdata"
P = H.load_module(H.BENCH / "program_spans.py")
T = H.load_module(H.BENCH / "trace.py")
TRACE = DATA / "serve_spans.xplane.pb"
SPANS = ("serve.step", "serve.sync", "ckpt.state", "ckpt.capture",
         "ckpt.capture.kernel", "ckpt.capture.d2h", "ckpt.capture.oplog",
         "ckpt.encode", "ckpt.encode.leaf", "ckpt.write", "ckpt.commit",
         "ckpt.restore.materialize", "ckpt.restore.fetch",
         "ckpt.restore.decode", "ckpt.restore.replay")


@pytest.fixture(scope="module")
def spans():
    return P.reduce(TRACE)


def test_program_spans_are_found(spans):
    assert set(SPANS) <= set(spans.counts)
    # one save, the link: its capture, encode and commit once each
    assert spans.counts["ckpt.capture"] == 1
    assert spans.counts["ckpt.encode"] == spans.counts["ckpt.commit"] == 1
    # four steps in the window and the restored engine's first
    assert spans.counts["serve.step"] == spans.counts["serve.sync"] == 5
    assert spans.seconds["ckpt.capture"] >= \
        spans.seconds["ckpt.capture.d2h"] > 0


def test_snapshot_gaps_name_the_program_span_under_them(spans):
    snap = [(label, sec) for label, sec in spans.gaps
            if label.split("/")[0] == "snapshot" and sec > 1e-4]
    assert snap
    assert all(label.startswith("snapshot/ckpt.") for label, _ in snap)
    steps = [label for label, sec in spans.gaps
             if label.split("/")[0] == "step" and sec > 1e-4]
    assert steps and all(label.startswith("step/serve.") for label in steps)


def test_idle_time_and_harness_labels_are_the_reductions(spans):
    s = T.summarize(TRACE)
    assert spans.window_s == s.window_s
    assert sum(spans.idle_by_label.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    coarse = {}
    for label, sec in spans.idle_by_label.items():
        k = label.split("/")[0]
        coarse[k] = coarse.get(k, 0.0) + sec
    assert coarse == pytest.approx(s.idle_by_span)


def test_a_trace_without_program_spans_keeps_the_harness_labels():
    old = DATA / "capture_kernels.xplane.pb"
    p, s = P.reduce(old), T.summarize(old)
    assert p.counts == {}
    assert p.idle_by_label == pytest.approx(s.idle_by_span)
    assert [g[0] for g in p.gaps] == [g[0] for g in s.gaps]


def test_stretches_name_the_innermost_spans():
    bench = [(0, 100, "snapshot"), (200, 300, "step")]
    prog = [(0, 100, "ckpt.capture"), (10, 40, "ckpt.capture.d2h"),
            (50, 90, "ckpt.capture.oplog"), (200, 300, "serve.step"),
            (250, 260, "serve.sync"), (400, 450, "serve.step")]
    assert P.stretches(bench, prog) == [
        (0, 10, "snapshot/ckpt.capture"),
        (10, 40, "snapshot/ckpt.capture.d2h"),
        (40, 50, "snapshot/ckpt.capture"),
        (50, 90, "snapshot/ckpt.capture.oplog"),
        (90, 100, "snapshot/ckpt.capture"),
        (200, 250, "step/serve.step"), (250, 260, "step/serve.sync"),
        (260, 300, "step/serve.step"), (400, 450, "none/serve.step")]
    # harness spans alone come back whole, so trace.py's labels stay
    assert P.stretches(bench, []) == bench


def test_a_gap_takes_the_stretch_that_overlaps_it_most():
    bench = [(0, 100, "snapshot")]
    prog = [(0, 100, "ckpt.capture"), (10, 40, "ckpt.capture.d2h")]
    merged = [(0, 12), (38, 60), (95, 100)]      # device busy
    gaps, idle = T._label_gaps(merged, P.stretches(bench, prog), 120e-9)
    assert [g[0] for g in gaps] == ["snapshot/ckpt.capture", "snapshot/"
                                    "ckpt.capture.d2h", "none"]
    assert [g[1] for g in gaps] == pytest.approx([35e-9, 26e-9, 20e-9])

"""The trace reduction on a small trace recorded on a TPU v5e: three
matrix products inside a ``bench.step`` span, then one fingerprint and
one fused-capture kernel inside a ``bench.snapshot`` span."""
from pathlib import Path

import pytest

import harness as H

TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "capture_kernels.xplane.pb"
T = H.load_module(H.BENCH / "trace.py")


@pytest.fixture(scope="module")
def summary():
    return T.summarize(TRACE)


def test_busy_and_window(summary):
    assert summary.devices == 1
    assert 0 < summary.busy_s < summary.window_s
    assert summary.window_s == pytest.approx(0.330104562)
    assert 0 < summary.idle_share < 1


def test_programs_and_kernels(summary):
    assert set(summary.programs) == {"jit__lambda", "jit__fingerprint_impl",
                                     "jit__fused_capture_impl"}
    fp = summary.kernels["_fingerprint_impl"]
    # reads the leaf as bf16[64,512,256], writes s32[64,1,128]
    assert fp["calls"] == 1
    assert fp["bytes"] == 64 * 512 * 256 * 2 + 64 * 128 * 4
    fused = summary.kernels["_fused_capture_impl"]
    assert fused["calls"] == 1 and fused["bytes"] > 64 * 512 * 256 * 2


def test_gaps_labelled_by_host_span(summary):
    assert {"step", "snapshot"} <= set(summary.idle_by_span)
    labels = [g[0] for g in summary.gaps]
    assert labels[0] == "snapshot"
    total_idle = sum(summary.idle_by_span.values())
    assert total_idle == pytest.approx(summary.window_s - summary.busy_s,
                                       rel=1e-6)
    b = summary.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_roofline_reader_is_a_share(summary):
    reader = H.load_module(H.BENCH / "metrics" / "capture_kernel_roofline.py")
    peaks = H.load_json(H.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    v = reader.read({"peaks": peaks}, summary)
    assert 0 < v <= 100
    assert reader.read({"peaks": peaks}, None) is None


def test_shape_bytes():
    assert T.shape_bytes("(bf16[4,8]{1,0:T(8,128)}, s32[2]) f32[]") == \
        4 * 8 * 2 + 2 * 4 + 4
    assert T.kernel_call("%add.1 = f32[4] add(f32[4] %a, f32[4] %b)") is None

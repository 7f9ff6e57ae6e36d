"""Each driver end to end at a ``-smoke`` size on the CPU, through its
function: set-up, the window with its saves, the wait, the restore and
the step after it, and the reference; every check passes."""
import math

import pytest


@pytest.mark.parametrize("name", ["serve-phi4-snap", "train-mamba2-snap"])
def test_driver_smoke_run_is_correct(smoke_ctx, name):
    ctx = smoke_ctx(name, seed=2 ** 33 + 17, seconds=2.0)
    res = ctx.cell.driver().run(ctx)
    bad = [c for c in res["checks"] if not c.ok]
    assert not bad, bad
    e2e = res["end_to_end"]
    assert set(e2e) >= {"durable_s", "resume_s", "setup_s"}
    assert all(math.isfinite(v) and v > 0 for v in e2e.values()), e2e
    run = res["run"]
    assert run["stalls"] and run["restore"]["materialize_s"] >= 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert not ctx.store.exists()


def _serve_traffic():
    import harness as H
    wl = H.load_json(H.BENCH / "workloads" / "serve-phi4-snap.json")
    return wl["traffic"], H.load_module(H.BENCH / "drivers" / "serve_snap.py")


def test_serve_lengths_come_from_the_seed():
    traffic, drv = _serve_traffic()

    def lengths(seed):
        return [(len(r["prompt"]), r["max_new"])
                for r in drv.requests(traffic, 200064, seed)]

    a, b = lengths(2 ** 32 + 5), lengths(2 ** 32 + 6)
    assert a == lengths(2 ** 32 + 5) and a != b
    plo, phi = traffic["prompt_len"]
    olo, ohi = traffic["output_len"]
    for p, o in a + b:
        assert plo <= p <= phi and olo <= o <= ohi
    prompts = sorted(p for p, _ in a + b)
    assert prompts[len(prompts) // 2] < (plo + phi) / 2   # a heavy tail


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 2 ** 33 + 17])
def test_serve_warmup_covers_every_admission_width(seed):
    traffic, drv = _serve_traffic()
    S, max_seq = traffic["slots"], traffic["max_seq"]
    want = set(drv.widths(traffic))
    for i, r in enumerate(drv.requests(traffic, 200064, seed)):
        hist = len(r["prompt"]) + (len(r["given"]) if i < S else 0) - 1
        assert drv._width(hist, max_seq) in want

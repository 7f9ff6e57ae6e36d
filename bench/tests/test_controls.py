"""Each cell's control at a ``-smoke`` size on the CPU, put in the
program's place, comes out not correct under the cell's own limits, as
it does on the chip at the cell's own size (``bench/controls.py``;
readings and limits in PERF.md)."""
import harness as H

CTL = H.load_module(H.BENCH / "controls.py", "bench_controls_under_test")


def test_serve_control_comes_out_not_correct(tmp_path):
    from conftest import SmokeCtx, smoke_cell
    r = CTL.serve_readings(smoke_cell("serve-phi4-snap"), 11, 2.0,
                           tmp_path / "store",
                           lambda c, s, sec, st: SmokeCtx(c, s, sec, st))
    assert r["program"]["correct"], r["program"]
    assert not r["control"]["correct"], r["control"]
    failed = [k for k, c in r["control"]["checks"].items()
              if not c["value"] <= c["limit"]]
    assert set(failed) <= {"logit_gap", "kv_err"} and failed, failed


def test_train_control_and_half_batch_separate_from_the_program(smoke_ctx):
    from conftest import smoke_cell
    ctx = smoke_ctx("train-mamba2-snap", seed=12, seconds=1.0)
    prog = ctx.cell.driver().run(ctx)["run"]["compare"]
    r = CTL.train_readings(smoke_cell("train-mamba2-snap"), 12)
    keys = ("loss_gap", "grad_err", "change_gap")
    for name in ("control_fp8", "fault_half_batch"):
        ratios = [r[name]["readings"][k] / max(prog[k], 1e-12) for k in keys]
        assert max(ratios) >= 3, (name, r[name], prog)

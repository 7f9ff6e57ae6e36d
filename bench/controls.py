"""Readings of each cell's control and planted faults, from which the
limits of ``correct`` are set. The benchmark's own runs never run this.

    python bench/controls.py --workload serve-phi4-snap --seeds 1,2,3 --seconds 40

The cell has to be listed in ``BENCHMARK.json``.

Serving: each seed is a whole run of the cell through its driver's
``serve``; its ``compare`` then judges the program, and again the
control put in the program's place (the reference in float8 weights:
the tokens it would have served and its keys and values), each under
the cell's limits. One line per seed: both sets of checks and the
``correct`` each gives.

Training: the reference in float8 weights, and the reference that takes
its loss over half of the batch, each put in the program's place and
compared with the float32 reference as the cell compares the program,
under the cell's limits.

The last line of standard output is one JSON object with every reading.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import harness as H  # noqa: E402


def _judged(checks) -> dict:
    return {"correct": H.correct(checks), "checks": H.checks_dict(checks)}


def serve_readings(cell: H.Cell, seed: int, seconds: float, store: Path,
                   ctx_factory) -> dict:
    drv = cell.driver()
    res, got = drv.serve(ctx_factory(cell, seed, seconds, store))
    program, _ = drv.compare(cell, got)
    control, _ = drv.compare(cell, got, control=True)
    return {"seed": seed,
            "program": _judged(res["checks"] + program),
            "control": _judged(res["checks"] + control)}


def train_readings(cell: H.Cell, seed: int) -> dict:
    cfg, ref = cell.config, cell.reference()
    traffic = cell.workload["traffic"]
    limits = cell.workload["check"]["limits"]
    s_weights, s_data = H.sub_seeds(seed, 2)
    n = traffic["checked_steps"]
    batches = [ref.batch(cfg, s_data, i, traffic["rows"], traffic["seq_len"])
               for i in range(n)]
    base = ref.train_reference(cfg, s_weights, batches, cfg["start_step"], n)
    out = {"seed": seed}
    for name, kw in (("control_fp8", {"quant": True}),
                     ("fault_half_batch", {"half_batch": True})):
        alt = ref.train_reference(cfg, s_weights, batches, cfg["start_step"],
                                  n, **kw)
        cmp = ref.compare(base, alt)
        out[name] = dict(
            _judged([H.Check(k, cmp[k], lim) for k, lim in limits.items()]),
            readings={k: cmp[k] for k in (
                "loss_gap", "grad_err", "change_gap", "grad_gap",
                "grad_err_worst_leaf", "grad_gap_leaf", "change_gap_leaf")})
    # a loss altered where it is produced (by 5%), and a step that leaves
    # the state unchanged, need no run: they read 0.05 on loss_gap and 1
    # on change_gap by construction
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    cell = H.find_cell(args.workload)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("controls: needs a TPU", file=sys.stderr)
        return 2
    run = H.load_module(BENCH / "run.py", "bench_run_entry")
    run.use_compile_cache()
    store = H.CHECKOUT / ".bench_store" / f"{cell.name}-controls"

    def make_ctx(c, seed, seconds, st):
        st.mkdir(parents=True, exist_ok=True)
        return run.Context(c, seed, seconds, False, st,
                           H.CHECKOUT / ".bench_trace" / "controls",
                           time.perf_counter())

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.workload["driver"] == "serve_snap":
            r = serve_readings(cell, seed, args.seconds, store, make_ctx)
        else:
            r = train_readings(cell, seed)
        print(json.dumps(r), flush=True)
        rows.append(r)
    print(json.dumps({"workload": cell.name, "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found from its name (see ``bench/harness.py``).
The run needs JAX to find TPUs, at least as many as the cell asks for;
otherwise it exits non-zero and prints no result. It never falls back
to the CPU.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics, each read by
``bench/metrics/<metric>.py``. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (with ``busy_s`` and ``window_s`` when traced), ``breakdown``
when traced, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(CHECKOUT / "src"))

import harness as H  # noqa: E402


class Context:
    """What a driver gets: the cell, the seed, the window's length, a
    record of host spans, the store directory, and hooks that
    open and close the measured window."""

    def __init__(self, cell: H.Cell, seed: int, seconds: float, trace: bool,
                 store: Path, trace_dir: Path, t_start: float) -> None:
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.store, self.trace_dir = trace, store, trace_dir
        self.t_start = t_start
        self.record = H.Record(tracing=trace)
        self.compiles = H.CompileCounter.install()

    def begin_window(self) -> None:
        if self.trace:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self.compiles.start()

    def end_window(self) -> None:
        self.compiles.stop()
        if self.trace:
            import jax
            jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        return H.memory_peak_bytes(self.cell.chips)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer_metrics(cell: H.Cell, run: dict, trace) -> dict:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer():
        reader = H.load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(run, trace)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = H.find_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs TPUs; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips; JAX found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    device = H.device_facts(cell.chips)
    peaks = H.peaks(device["kind"])
    store = CHECKOUT / ".bench_store" / cell.name
    shutil.rmtree(store, ignore_errors=True)
    store.mkdir(parents=True)
    trace_dir = CHECKOUT / ".bench_trace" / cell.name
    H.log(f"{cell.name}: {device['count']} x {device['kind']}, compile "
          f"cache {cache}, store {store}")

    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), store,
                  trace_dir, T_START)
    try:
        res = cell.driver().run(ctx)
    finally:
        shutil.rmtree(store, ignore_errors=True)
    compiles = ctx.compiles.window()
    print(f"compiles_in_window: {json.dumps(compiles)}", flush=True)
    run = dict(res["run"], peaks=peaks)

    if args.trace:
        T = H.load_module(BENCH / "trace.py")
        summary = T.summarize(trace_dir, spans_prefix="bench.")
        H.log(f"trace: busy {summary.busy_s:.3f}s of {summary.window_s:.3f}s; "
              f"idle by span {summary.idle_by_span}; kernels "
              f"{summary.kernels}")
        metrics = per_layer_metrics(cell, run, summary)
        device = dict(device, busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end()}
        breakdown = None
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])

    checks = res["checks"]
    correct = H.correct(checks) and all(
        math.isfinite(v["value"]) for v in metrics.values())
    out = {"correct": bool(correct), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = H.checks_dict(checks)
    H.log(f"run took {time.perf_counter() - T_START:.1f}s")
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

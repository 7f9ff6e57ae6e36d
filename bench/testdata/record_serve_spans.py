"""Record ``serve_spans.xplane.pb`` on one TPU chip: a few engine steps,
one chained save and its restore of the phi4-mini smoke model, under
the profiler with the benchmark's options.

    python3 bench/testdata/record_serve_spans.py [--out <path>]

Eight slots of 2048 positions make each KV leaf 2 MiB, above the
pipeline's dirty-chunk threshold, so a base runs the fingerprint kernel
and a link the fused capture kernel. Saves come every 4 steps with
``chain=2``; three are taken before the trace (compiling both capture
paths), and the traced window holds steps 13-16, the link saved at 16
and waited for (its encode, writes and commit run on the pipeline's
threads), then a restore of it stepped once. Steps, saves and the
restore sit inside ``bench.step``, ``bench.snapshot`` and
``bench.restore`` spans, as in a benchmark run.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(HERE / "serve_spans.xplane.pb"))
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("record_serve_spans: needs a TPU", file=sys.stderr)
        return 2
    from repro.api import CheckpointSession, Policy
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine

    cfg = get_smoke_config("phi4-mini-3.8b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    store = Path(tempfile.mkdtemp())
    trace_dir = Path(tempfile.mkdtemp())
    rec = H.Record(tracing=True)
    sess = CheckpointSession(f"localfs:{store}", Policy(chain=2, interval=4))
    eng = sess.attach(ServingEngine.create(
        "phi4-mini-3.8b-smoke", params, (1, 1), n_slots=8, max_seq=2048,
        manager=sess.manager))
    rng = np.random.RandomState(0)
    for rid in range(8):
        eng.submit(Request(rid=rid, max_new=64,
                           prompt=rng.randint(0, cfg.vocab_size, 24)))

    def step():
        with rec.span("step"):
            eng.step()
        with rec.span("snapshot"):
            return sess.maybe_snapshot()

    for _ in range(12):             # saves at 4 (base), 8 (link), 12 (base)
        step()
    sess.wait()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    for _ in range(4):              # the link at step 16
        h = step()
    assert h is not None and h.step == 16
    sess.wait()
    with rec.span("restore"):
        eng2 = CheckpointSession(f"localfs:{store}").restore(
            step=16, expect_kind="serving", params=params)
        eng2.step()
    jax.profiler.stop_trace()
    sess.close()
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(H.load_module(H.BENCH / "trace.py").find_xplane(trace_dir),
                out)
    shutil.rmtree(store, ignore_errors=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

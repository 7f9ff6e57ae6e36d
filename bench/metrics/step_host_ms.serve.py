"""Host time per engine step while the device waits, over the traced
window: the ``serve.step`` spans' seconds less the ``serve.sync`` spans'
(the blocking read of each step's next tokens), per ``serve.step``, read
from the window's trace (``bench/program_spans.py``). Reading it also
prints to standard error the window's idle seconds per ``<harness
span>/<program span>`` label, and the count of each program span."""
import harness as H

P = H.load_module(H.BENCH / "program_spans.py")


def read(run, trace):
    if trace is None:
        return None
    ps = P.newest()
    if ps is None or not ps.counts.get("serve.step"):
        return None
    idle = sorted(ps.idle_by_label.items(), key=lambda kv: -kv[1])
    H.log("program spans: idle by label "
          + ", ".join(f"{k} {v:.3f}s" for k, v in idle))
    H.log("program spans: counts " + ", ".join(
        f"{k} {n}" for k, n in sorted(ps.counts.items())))
    host = ps.seconds["serve.step"] - ps.seconds.get("serve.sync", 0.0)
    return 1e3 * host / ps.counts["serve.step"]

"""Idle gaps of the device labelled by the program's own spans.

``bench/trace.py`` labels each idle gap of the device by the harness
span open in it (``snapshot``, ``step``, ...). The program marks its
layers with spans of its own on the same clock (``src/repro/spans.py``:
``ckpt.*`` in the checkpoint pipeline, ``serve.*`` in the serving
engine). Here each gap is labelled ``<harness span>/<program span>``,
from the host thread that carries the harness spans: the thread whose
enqueues the device waits on. That thread's spans are cut into stretches
at every span's edge, each stretch named by the innermost harness span
and the innermost program span open in it, and ``trace.py``'s own rule
names each gap by the stretch that overlaps it most. Where no program
span is open, the harness label stays.

    python3 bench/program_spans.py <trace.xplane.pb, or a directory>

prints one JSON object: idle seconds per label, the longest gaps, and
the count and summed seconds of each program span over every thread.
The ``step_host_ms.serve`` metric reads the newest trace of a traced
run through ``newest()``.
"""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as H  # noqa: E402

T = H.load_module(H.BENCH / "trace.py")

PREFIXES = ("ckpt.", "serve.")
TRACES = H.CHECKOUT / ".bench_trace"    # where bench/run.py keeps a trace

Span = Tuple[float, float, str]


@dataclass
class ProgramSpans:
    window_s: float
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_label: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    seconds: Dict[str, float] = field(default_factory=dict)

    def report(self) -> Dict[str, object]:
        return {"window_s": self.window_s,
                "idle_by_label": dict(sorted(self.idle_by_label.items(),
                                             key=lambda kv: -kv[1])),
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]],
                "spans": {k: {"count": self.counts[k],
                              "seconds": self.seconds[k]}
                          for k in sorted(self.counts)}}


def reduce(path, bench_prefix: str = "bench.",
           prefixes: Tuple[str, ...] = PREFIXES) -> ProgramSpans:
    """Reduce the newest trace under ``path`` (a file or a directory)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(T.find_xplane(path)))
    window = None
    ops: Optional[List[Tuple[float, float]]] = None   # the first device
    threads: List[Tuple[List[Span], List[Span]]] = []
    counts: Dict[str, int] = {}
    seconds: Dict[str, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and ops is None and \
                plane.name[len("/device:TPU:"):].isdigit():
            ops = [(e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = (int(st["profile_stop_time"])
                          - int(st["profile_start_time"])) / 1e9
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                bench: List[Span] = []
                prog: List[Span] = []
                for e in line.events:
                    if e.name.startswith(bench_prefix):
                        bench.append((e.start_ns, e.end_ns,
                                      e.name[len(bench_prefix):]))
                    elif e.name.startswith(prefixes):
                        prog.append((e.start_ns, e.end_ns, e.name))
                        counts[e.name] = counts.get(e.name, 0) + 1
                        seconds[e.name] = seconds.get(e.name, 0.0) \
                            + (e.end_ns - e.start_ns) / 1e9
                threads.append((bench, prog))
    if ops is None:
        raise ValueError("the trace holds no TPU device plane")
    merged = T._union(ops)
    if window is None:
        window = ((merged[-1][1] - merged[0][0]) / 1e9) if merged else 0.0
    # the thread that carries the harness spans, and its program spans
    bench, prog = max(threads, key=lambda t: len(t[0]), default=([], []))
    gaps, idle_by = T._label_gaps(merged, stretches(bench, prog), window)
    return ProgramSpans(window_s=window, gaps=gaps, idle_by_label=idle_by,
                        counts=counts, seconds=seconds)


def stretches(bench: List[Span], prog: List[Span]) -> List[Span]:
    """One thread's spans cut at every edge: each stretch in which a span
    is open, named ``<innermost harness span>/<innermost program span>``
    (``none`` for a missing harness span, the harness name alone for a
    missing program span). Spans of one thread nest, so the innermost
    open span of a kind is the last one opened."""
    # at one instant, ends before starts, and an outer span opens first
    edges = sorted(ev for k, spans in enumerate((bench, prog))
                   for i, (s, e, _) in enumerate(spans) if e > s
                   for ev in ((s, 1, -e, k, i), (e, 0, 0, k, i)))
    open_: Tuple[List[int], List[int]] = ([], [])
    out: List[Span] = []
    t0 = None
    for t, is_start, _, k, i in edges:
        if t0 is not None and t > t0 and (open_[0] or open_[1]):
            label = bench[open_[0][-1]][2] if open_[0] else "none"
            if open_[1]:
                label = f"{label}/{prog[open_[1][-1]][2]}"
            if out and out[-1][2] == label and out[-1][1] == t0:
                out[-1] = (out[-1][0], t, label)
            else:
                out.append((t0, t, label))
        t0 = t
        if is_start:
            open_[k].append(i)
        else:
            open_[k].remove(i)
    return out


def newest() -> Optional[ProgramSpans]:
    """The reduction of the newest trace ``bench/run.py`` keeps, or None
    if it keeps none."""
    try:
        return reduce(TRACES)
    except FileNotFoundError:
        return None


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]).report()))

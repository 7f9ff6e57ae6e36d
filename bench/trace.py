"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, per-program and per-kernel device time
with the bytes each kernel call moves, and the idle gaps labelled by the
harness's host span that was open during them.

The trace is read with ``jax.profiler.ProfileData`` alone. Device planes
are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO op, named by the op's HLO text (which carries its operand
and result shapes), and their ``XLA Modules`` line one event per program
run. Host planes hold the harness's ``jax.profiler.TraceAnnotation``
spans; all events share the profile's clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "f32": 4, "s32": 4, "u32": 4,
               "f64": 8, "s64": 8, "u64": 8}
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_SUFFIX = re.compile(r"\(\d+\)$")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text`` (``bf16[4,8]``)."""
    total = 0
    for dt, dims in _SHAPE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def kernel_call(hlo: str) -> Optional[Tuple[str, int]]:
    """(kernel name, bytes it reads and writes) of a Pallas kernel op,
    from its HLO text: the op's name, and the shapes of its results and
    operands. None for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return None
    lhs, _, rhs = hlo.partition(" = ")
    name = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
    head, _, rest = rhs.partition("custom-call(")
    operands = rest.split("), custom_call_target")[0]
    return name, shape_bytes(head) + shape_bytes(operands)


def find_xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return Path(found[-1])


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                                  # averaged over devices
    devices: int
    programs: Dict[str, float] = field(default_factory=dict)
    kernels: Dict[str, Dict[str, float]] = field(default_factory=dict)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> Dict[str, list]:
        progs = sorted(self.programs.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in progs],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def summarize(path, spans_prefix: str = "bench.") -> Summary:
    """Reduce the newest trace under ``path`` (a file or a directory)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(find_xplane(path)))
    window = None
    spans: List[Tuple[float, float, str]] = []
    dev_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev_planes.append(plane)
        elif plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                window = (int(st["profile_stop_time"])
                          - int(st["profile_start_time"])) / 1e9
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(spans_prefix):
                        spans.append((e.start_ns, e.end_ns,
                                      e.name[len(spans_prefix):]))
    if not dev_planes:
        raise ValueError("the trace holds no TPU device plane")

    busy_total = 0.0
    programs: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    merged0: List[Tuple[float, float]] = []
    t_lo = t_hi = None
    for k, plane in enumerate(dev_planes):
        ops: List[Tuple[float, float]] = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    s, d = e.start_ns, e.duration_ns
                    ops.append((s, s + d))
                    name = e.name
                    if "custom-call(" in name:
                        kc = kernel_call(name)
                        if kc is not None:
                            kk = kernels.setdefault(
                                kc[0], {"calls": 0, "seconds": 0.0,
                                        "bytes": 0})
                            kk["calls"] += 1
                            kk["seconds"] += d / 1e9
                            kk["bytes"] += kc[1]
            elif line.name == "XLA Modules":
                for e in line.events:
                    n = _SUFFIX.sub("", e.name)
                    programs[n] = programs.get(n, 0.0) + e.duration_ns / 1e9
        merged = _union(ops)
        busy_total += sum(b - a for a, b in merged) / 1e9
        if k == 0:
            merged0 = merged
        if merged:
            t_lo = merged[0][0] if t_lo is None else min(t_lo, merged[0][0])
            t_hi = merged[-1][1] if t_hi is None else max(t_hi, merged[-1][1])
    n_dev = len(dev_planes)
    if window is None:
        window = ((t_hi or 0) - (t_lo or 0)) / 1e9
    gaps, idle_by = _label_gaps(merged0, spans, window)
    return Summary(window_s=window, busy_s=busy_total / n_dev,
                   devices=n_dev,
                   programs={k: v / n_dev for k, v in programs.items()},
                   kernels=kernels, gaps=gaps, idle_by_span=idle_by)


def _label_gaps(merged: List[Tuple[float, float]],
                spans: List[Tuple[float, float, str]], window_s: float):
    """Every idle gap between device ops on the first device, labelled
    by the host span that overlaps it most ("none" if no span does);
    returns the gaps longest first, and idle seconds per label."""
    gaps = []
    edges = [(0.0, 0.0)] + merged + [(window_s * 1e9, window_s * 1e9)]
    for (_, a), (b, _) in zip(edges, edges[1:]):
        if b > a:
            gaps.append((a, b))
    spans = sorted(spans)
    out, by = [], {}
    j0 = 0
    for a, b in gaps:
        best, label = 0.0, "none"
        while j0 < len(spans) and spans[j0][1] < a:
            j0 += 1
        j = j0
        while j < len(spans) and spans[j][0] < b:
            ov = min(b, spans[j][1]) - max(a, spans[j][0])
            if ov > best:
                best, label = ov, spans[j][2]
            j += 1
        sec = (b - a) / 1e9
        out.append((label, sec))
        by[label] = by.get(label, 0.0) + sec
    out.sort(key=lambda x: -x[1])
    return out, by

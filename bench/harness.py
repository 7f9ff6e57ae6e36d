"""Shared pieces of the benchmark: finding a cell's files by name, seeds,
host spans and counters, the compile counter, device facts, and the
checks that decide ``correct``.

Everything a cell needs is found from its name in ``BENCHMARK.json``:

* ``bench/workloads/<cell>.json``  the driver, traffic parameters, limits
* ``bench/configs/<config>/config.json``  sizes, deployment, ``Policy``
* ``bench/configs/<config>/reference.py``  weights from the seed and the
  plain float32 reference
* ``bench/configs/<config>/flops.py``  model FLOPs per token
* ``bench/drivers/<driver>.py``  the step loop the cell runs
* ``bench/metrics/<metric>.py``  one reader per per-layer metric
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: Optional[str] = None):
    """Import a file by path (metric and config files carry dots and
    dashes in their names, so they are not importable as packages)."""
    path = Path(path).resolve()
    name = name or "bench_" + "_".join(path.with_suffix("").parts[-3:]) \
        .replace("-", "_").replace(".", "_") + f"_{abs(hash(str(path)))}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark_spec(checkout: Path = CHECKOUT) -> Dict[str, Any]:
    return load_json(checkout / "BENCHMARK.json")


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    entry: Dict[str, Any]           # the BENCHMARK.json workload entry
    workload: Dict[str, Any]        # bench/workloads/<cell>.json
    config: Dict[str, Any]          # bench/configs/<config>/config.json
    config_dir: Path
    spec: Dict[str, Any]            # the whole BENCHMARK.json

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def driver(self):
        return load_module(BENCH / "drivers" / f"{self.workload['driver']}.py")

    def reference(self):
        return load_module(self.config_dir / "reference.py")

    def flops(self):
        return load_module(self.config_dir / "flops.py")

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict[str, Any]]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if self.name in m.get("workloads", [self.name])
                and m["moves"] in e2e]


def find_cell(name: str, checkout: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    spec = benchmark_spec(checkout)
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    entry = entries[0]
    bench = checkout / "bench"
    workload = load_json(bench / "workloads" / f"{name}.json")
    if workload["traffic"]["name"] != entry["traffic"]:
        raise ValueError(f"{name}: BENCHMARK.json names traffic "
                         f"{entry['traffic']!r}, the workload file "
                         f"{workload['traffic']['name']!r}")
    if workload["config"] != entry["config"]:
        raise ValueError(f"{name}: config {entry['config']!r} in "
                         f"BENCHMARK.json, {workload['config']!r} in the "
                         "workload file")
    cdir = bench / "configs" / entry["config"]
    return Cell(name, entry, workload, load_json(cdir / "config.json"), cdir,
                spec)


def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit seeds from one ``--seed`` of any size."""
    import numpy as np
    return [int(s) & 0x7FFFFFFF
            for s in np.random.SeedSequence(int(seed)).generate_state(n)]


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------

@dataclass
class Record:
    """The harness's host spans around its calls into the program's
    layers (admission, engine or train step, snapshot, restore)."""
    tracing: bool = False

    def span(self, name: str):
        """Under ``--trace 1`` the span lands in the profiler's trace as
        ``bench.<name>``, on the clock of the device events, so idle gaps
        can be labelled by what the host was doing."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")


class CompileCounter:
    """Counts XLA programs compiled (or loaded from the persistent cache)
    and true compiles (persistent-cache misses) through JAX's monitoring
    events. Read ``window()`` for what happened between ``start`` and
    ``stop``."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self) -> None:
        self.programs = 0
        self.misses = 0
        self._w0 = self._w1 = None

    @classmethod
    def install(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax
            c = cls()

            def on_duration(event, duration, **kw):
                if event == "/jax/core/compile/backend_compile_duration":
                    c.programs += 1

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_misses":
                    c.misses += 1

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._installed = c
        return cls._installed

    def start(self) -> None:
        self._w0 = (self.programs, self.misses)
        self._w1 = None

    def stop(self) -> None:
        self._w1 = (self.programs, self.misses)

    def window(self) -> Dict[str, int]:
        end = self._w1 or (self.programs, self.misses)
        return {"programs": end[0] - self._w0[0],
                "compiles": end[1] - self._w0[1]}


# ---------------------------------------------------------------------------
# device facts
# ---------------------------------------------------------------------------

def device_facts(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices()[:chips])


def peaks(kind: str) -> Dict[str, Any]:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# checks that decide `correct`
# ---------------------------------------------------------------------------

@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (not math.isnan(self.value)) and self.value <= self.limit


def correct(checks: List[Check]) -> bool:
    """Whether every number compared is within its limit."""
    return all(c.ok for c in checks)


def checks_dict(checks: List[Check]) -> Dict[str, Dict[str, float]]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def tree_digest_fn():
    """A jitted digest of a pytree of arrays: per leaf, a plain and a
    position-weighted wrapping sum of the raw bits, so two states are
    compared without moving them off the device."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        x = jnp.asarray(x)
        bits = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, bits).astype(jnp.uint32).ravel()
        w = (jnp.arange(u.size, dtype=jnp.uint32) % jnp.uint32(65521)) \
            + jnp.uint32(1)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    return jax.jit(lambda tree: [leaf(x) for x in jax.tree.leaves(tree)])


def now() -> float:
    return time.perf_counter()


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)

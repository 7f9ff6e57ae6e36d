"""CPU rehearsal helpers: each cell at a ``-smoke`` size, driven through
its driver's function (not through ``run.py``)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
for p in (str(BENCH), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness as H  # noqa: E402

SMOKE = {
    "phi4-mini-3.8b": {
        "arch": "phi4-mini-3.8b-smoke", "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256},
    "mamba2-780m": {
        "arch": "mamba2-780m-smoke", "d_model": 64, "n_layer": 2,
        "d_state": 16, "headdim": 16, "vocab_size": 256},
}
SMOKE_TRAFFIC = {
    "serve-phi4-snap": {"slots": 2, "max_seq": 64, "prompt_len": [8, 16],
                        "output_len": [16, 24], "stagger_steps": 12,
                        "pool": 256},
    "train-mamba2-snap": {"rows": 2, "seq_len": 64, "checked_steps": 3},
}
SMOKE_POLICY = {"serve-phi4-snap": {"chain": 2, "interval": 4},
                "train-mamba2-snap": {"chain": 1, "interval": 500}}


def smoke_cell(name: str, checkout: Path = CHECKOUT) -> H.Cell:
    """The cell of ``name`` with the program's smoke-size model, a short
    traffic mix and a cadence that saves inside a short window."""
    wl = H.load_json(checkout / "bench" / "workloads" / f"{name}.json")
    entry = {"name": name, "config": wl["config"],
             "traffic": wl["traffic"]["name"], "chips": 1, "why": wl["why"]}
    cdir = checkout / "bench" / "configs" / wl["config"]
    cfg = H.load_json(cdir / "config.json")
    cfg.update(SMOKE[wl["config"]])
    cfg["policy"] = SMOKE_POLICY[name]
    if "start_step" in cfg:
        cfg["start_step"] = 500 - 3 - 2
    wl["traffic"].update(SMOKE_TRAFFIC[name])
    wl["check"]["sample"] = 8
    spec = dict(H.benchmark_spec(checkout), workloads=[entry])
    return H.Cell(name, entry, wl, cfg, cdir, spec)


class SmokeCtx:
    """The driver's context without the chip: no profiler, no device
    memory statistics."""

    def __init__(self, cell, seed: int, seconds: float, store: Path):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace = False
        self.store = store
        self.t_start = time.perf_counter()
        self.record = H.Record()
        self.compiles = H.CompileCounter.install()

    def begin_window(self):
        self.compiles.start()

    def end_window(self):
        self.compiles.stop()

    def memory_peak(self):
        return 0


@pytest.fixture
def smoke_ctx(tmp_path):
    def make(name: str, seed: int = 7, seconds: float = 4.0):
        return SmokeCtx(smoke_cell(name), seed, seconds, tmp_path / "store")
    return make

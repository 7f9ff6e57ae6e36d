"""A cell, a configuration and a per-layer metric added as files (and
entries in BENCHMARK.json) are found by name, with no existing file of
``bench/`` edited."""
import hashlib
import json
import shutil
from pathlib import Path

import harness as H

CHECKOUT = Path(__file__).resolve().parents[2]


def _digests(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_cell_config_and_metric_are_found(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "bench"
    before = _digests(bench)

    shutil.copytree(bench / "configs" / "phi4-mini-3.8b",
                    bench / "configs" / "phi4-mini-copy")
    wl = json.loads((bench / "workloads" / "serve-phi4-snap.json").read_text())
    wl["config"] = "phi4-mini-copy"
    wl["traffic"]["name"] = "chat-closed8"
    wl["traffic"]["output_len"] = [32, 256]
    (bench / "workloads" / "serve-phi4-chat.json").write_text(json.dumps(wl))
    (bench / "metrics" / "tokens.serve.py").write_text(
        "def read(run, trace):\n    return run.get('tokens')\n")

    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="phi4-mini-copy",
                                file="bench/configs/phi4-mini-copy/config.json"))
    spec["workloads"].append({"name": "serve-phi4-chat",
                              "config": "phi4-mini-copy",
                              "traffic": "chat-closed8", "chips": 1,
                              "why": "short outputs"})
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("serve-phi4-chat")
    spec["per_layer"].append({"name": "tokens.serve", "unit": "tokens",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step",
                              "moves": "serve_tokens_per_s",
                              "workloads": ["serve-phi4-chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = H.find_cell("serve-phi4-chat", tmp_path)
    assert cell.config_dir == bench / "configs" / "phi4-mini-copy"
    assert cell.workload["traffic"]["output_len"] == [32, 256]
    assert hasattr(cell.driver(), "run")
    assert hasattr(cell.reference(), "served_gaps")
    assert hasattr(cell.flops(), "decode_token")
    names = [m["name"] for m in cell.per_layer()]
    assert "tokens.serve" in names and "stall_s.serve" not in names
    reader = H.load_module(bench / "metrics" / "tokens.serve.py")
    assert reader.read({"tokens": 5}, None) == 5
    assert {m["name"] for m in cell.end_to_end()} == {
        "serve_tokens_per_s", "durable_s", "resume_s", "setup_s"}

    after = _digests(bench)
    assert all(after[p] == d for p, d in before.items())

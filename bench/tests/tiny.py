"""A checkout-shaped directory holding every cell of ``BENCHMARK.json`` at a
size a CPU test can run: the same drivers, traffic kinds and checks, with
small widths, few layers, short prompts and short sequences."""

from __future__ import annotations

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

SMALL_LM = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                vocab_size=512)
SMALL_TRAFFIC = {
    "serve_queue": dict(requests=3, batch=2, gen=8, cache_len=16),
    "train_steps": dict(trace_seconds=0.3),
    "task_graph": dict(warmup_graphs=2, trace_seconds=0.3),
}


def _read(path: Path) -> dict:
    return json.loads(path.read_text())


def make_root(tmp: Path, lm: dict = None, traffic: dict = None) -> Path:
    """Write the small checkout under ``tmp``; returns its root. ``lm`` and
    ``traffic`` (keyed by traffic kind) override the small sizes."""
    spec = _read(REPO / "BENCHMARK.json")
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    for entry in spec["configs"]:
        c = _read(REPO / entry["file"])
        if "hidden_size" in c:
            c.update(SMALL_LM, **(lm or {}))
        if "seq" in c:
            c["seq"] = 32
        (tmp / entry["file"]).write_text(json.dumps(c))
    for cell in spec["workloads"]:
        t = _read(REPO / "bench" / "traffic" / f"{cell['traffic']}.json")
        t.update(SMALL_TRAFFIC[t["kind"]])
        if t["kind"] == "serve_queue":
            t["prompt"] = max(2, t["prompt"] // 60)
        t.update((traffic or {}).get(t["kind"], {}))
        (tmp / "bench" / "traffic" / f"{cell['traffic']}.json").write_text(
            json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp

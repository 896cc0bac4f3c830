"""What every driver is given and what it hands back."""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

# JAX's own compile events of this process: cache hits and misses counted,
# cache reads, tracing, lowering and compiling summed in seconds.
COMPILES: collections.Counter = collections.Counter()
_watching = []


def watch_compiles() -> None:
    """Count JAX's compile events into ``COMPILES`` (once a process)."""
    if _watching:
        return
    from jax import monitoring

    def on_event(event, **_):
        COMPILES[event.rsplit("/", 1)[-1]] += 1

    def on_duration(event, seconds, **_):
        COMPILES[event.rsplit("/", 1)[-1]] += seconds

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _watching.append(True)


@dataclasses.dataclass
class Context:
    cell: dict            # the BENCHMARK.json entry, with its files read
    seed: int
    seconds: float
    t_start: float        # perf_counter at process start
    tracer: Any           # bench.trace.Tracer
    devices: list
    # (phase, perf_counter at its end, COMPILES then), in order
    marks: list = dataclasses.field(default_factory=list)

    @property
    def config(self) -> dict:
        return self.cell["config_file"]

    @property
    def traffic(self) -> dict:
        return self.cell["traffic_file"]

    def rng(self, *stream: int) -> np.random.Generator:
        """A generator for one purpose of this run, from the seed."""
        return np.random.default_rng([self.seed, *stream])

    def limit(self, name: str) -> float:
        return self.config["checks"][name]

    def mark(self, phase: str) -> float:
        """End a phase of set-up now; returns the time."""
        now = time.perf_counter()
        self.marks.append((phase, now, dict(COMPILES)))
        return now

    def setup_report(self) -> dict:
        """Seconds of each set-up phase, and JAX's compile events up to
        the last mark (the window's opening)."""
        phases, prev = {}, self.t_start
        for phase, t, _ in self.marks:
            phases[phase] = t - prev
            prev = t
        compiles = self.marks[-1][2] if self.marks else {}
        return {"phases_s": phases,
                "cache_hits": compiles.get("cache_hits", 0),
                "cache_misses": compiles.get("cache_misses", 0),
                "cache_read_s": compiles.get("cache_retrieval_time_sec", 0.0),
                "trace_s": compiles.get("jaxpr_trace_duration", 0.0),
                "lower_s": compiles.get("jaxpr_to_mlir_module_duration", 0.0),
                "compile_s": compiles.get("backend_compile_duration", 0.0)}


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    facts: Dict[str, Any]            # what the per-layer readers read
    checks: List[Tuple[str, float, float]]   # (name, value, limit)
    device: dict                     # platform, kind, count, memory peak
    trace_path: Optional[str] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            value == value and value <= limit   # NaN is never correct
            for _, value, limit in self.checks)


def device_now(devices) -> dict:
    """Device identity and the fullest chip's peak memory so far."""
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in stats), default=0)}


def peaks(kind: str) -> dict:
    """The peaks table's entry for a device kind; an unknown kind is an
    error, never a default."""
    import json
    from pathlib import Path

    table = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def dense_model_config(c: dict, **program):
    """The program's ``ModelConfig`` for a dense configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=c["rope_theta"],
        act=c["hidden_act"], tie_embeddings=c["tie_word_embeddings"],
        source=c["source"], **program)

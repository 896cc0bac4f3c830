"""The program's own spans in a traced run, for the per-layer metrics that
read them.

While a ``jax.profiler`` trace records, the program marks its layers with
``TraceAnnotation`` spans (``repro.runtime.metrics.span``) whose names
start with one of ``PROGRAM_PREFIXES`` and whose ids (``task``, ``rid``,
``wave``, ``pos``, ``name``) are the events' stats. ``bench.trace`` keeps
only the benchmark's ``bench.`` spans; this module reads the same
``.xplane.pb`` again for both kinds and gives, over the window:

- for each program span name: how many ended in the window (``count``),
  their seconds in it (``seconds``), their self seconds, less the program
  spans they hold on the same host line (``self_s``), and the device's
  idle seconds charged to them (``idle_s``);
- ``idle_by_span``: the idle seconds of device 0, each gap charged to the
  innermost span of either kind that holds its middle (``host.other``
  where none does), the rule of ``bench.trace``;
- ``handoff_s``: for each submitted task, the seconds from the end of its
  ``task.submit`` to the start of the ``task.run`` with the same ``task``
  id (negative where the task started before its submit returned).

A reader gets the run's ``bench.trace`` reduction, which names no file.
``of_run`` takes the newest trace under ``.bench_out/trace/`` and accepts
it only where its window is the one that reduction measured. To print the
reduction of a trace by hand::

    python3 -m bench.spans <file.xplane.pb>
"""

from __future__ import annotations

import glob
import heapq
import json
import math
import os
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

from bench import trace as tr

ROOT = Path(__file__).resolve().parents[1]
PROGRAM_PREFIXES = ("task.", "graph.", "serve.", "data.")

_cache: Dict[tuple, dict] = {}


class Span(tuple):
    """A host span as ``(name, start, end)``, with the host line (thread)
    it ran on, as (plane, index), and its stats."""

    def __new__(cls, name: str, start: float, end: float, line=0,
                stats: Optional[dict] = None):
        self = super().__new__(cls, (name, start, end))
        self.line = line
        self.stats = stats or {}
        return self


def is_program_span(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def read_events(path: str):
    """(device ops by plane, host spans) of an ``.xplane.pb``, as
    ``bench.trace.read_events`` reads them, but with the program's spans
    beside the benchmark's, each a ``Span``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (tr.op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for index, line in enumerate(plane.lines):
                spans.extend(Span(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  (plane.name, index), dict(e.stats))
                             for e in line.events
                             if e.name.startswith(tr.SPAN_PREFIX)
                             or is_program_span(e.name))
    return ops, spans


def attribute_all(idle: List[tr.Interval], spans: list) -> List[str]:
    """``bench.trace.attribute`` of each gap of ``idle``, in one sweep
    over the spans in order of their start."""
    order = sorted(spans, key=lambda s: s[1])
    mids = sorted(range(len(idle)), key=lambda i: idle[i][0] + idle[i][1])
    out = ["host.other"] * len(idle)
    held, k = [], 0         # heap of (length, name, end) of open spans
    for i in mids:
        mid = (idle[i][0] + idle[i][1]) / 2
        while k < len(order) and order[k][1] <= mid:
            name, a, b = order[k]
            heapq.heappush(held, (b - a, name, b))
            k += 1
        while held and held[0][2] <= mid:
            heapq.heappop(held)
        if held:
            out[i] = held[0][1]
    return out


def self_seconds(spans: list, lo: float, hi: float) -> Dict[int, float]:
    """Each span's seconds in [lo, hi] less those of the spans it holds
    directly on the same line, by the span's index in ``spans``."""
    out: Dict[int, float] = {}
    by_line: Dict[object, list] = defaultdict(list)
    for i, s in enumerate(spans):
        by_line[getattr(s, "line", 0)].append(i)
    for members in by_line.values():
        members.sort(key=lambda i: (spans[i][1], -spans[i][2]))
        stack: List[int] = []
        for i in members:
            _, a, b = spans[i]
            while stack and spans[stack[-1]][2] <= a:
                stack.pop()
            inside = max(0.0, min(b, hi) - max(a, lo))
            out[i] = out.get(i, 0.0) + inside
            if stack:
                out[stack[-1]] = out.get(stack[-1], 0.0) - inside
            stack.append(i)
    return out


def handoffs(spans: list) -> List[float]:
    """Seconds from the end of each ``task.submit`` to the start of the
    ``task.run`` with the same ``task`` id."""
    submitted: Dict[object, float] = {}
    out = []
    for s in sorted(spans, key=lambda s: s[1]):
        task = getattr(s, "stats", {}).get("task")
        if task is None:
            continue
        if s[0] == "task.submit":
            submitted[task] = s[2]
        elif s[0] == "task.run" and task in submitted:
            out.append(s[1] - submitted.pop(task))
    return out


def window(ops: Dict[str, list], spans: list):
    """The window ``bench.trace.reduce`` measures: the ``bench.window``
    span, else the extent of the device ops."""
    windows = [(a, b) for name, a, b in spans if name == tr.WINDOW_SPAN]
    all_ops = [(a, b) for events in ops.values() for _, a, b in events]
    if windows:
        return windows[0]
    if all_ops:
        return min(a for a, _ in all_ops), max(b for _, b in all_ops)
    raise ValueError("trace has neither a window span nor device ops")


def reduce(ops: Dict[str, list], spans: list) -> dict:
    """The program spans of one trace, with the idle gaps charged to the
    innermost span of either kind."""
    lo, hi = window(ops, spans)
    planes = sorted(ops)
    idle = (tr.gaps(tr.union(tr.clip([(a, b) for _, a, b in ops[planes[0]]],
                                     lo, hi)), lo, hi)
            if planes else [(lo, hi)])
    inner = [s for s in spans if s[0] != tr.WINDOW_SPAN]
    idle_by_span: Dict[str, float] = defaultdict(float)
    for g, name in zip(idle, attribute_all(idle, inner)):
        idle_by_span[name] += g[1] - g[0]

    ours = [s for s in inner if is_program_span(s[0]) and s[2] > lo
            and s[1] < hi]
    own = self_seconds(ours, lo, hi)
    names: Dict[str, dict] = {}
    for i, (name, a, b) in enumerate(ours):
        rec = names.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "self_s": 0.0, "idle_s": 0.0})
        rec["count"] += int(lo < b <= hi)
        rec["seconds"] += min(b, hi) - max(a, lo)
        rec["self_s"] += own[i]
    for name, rec in names.items():
        rec["idle_s"] = idle_by_span.get(name, 0.0)
    return {"window_s": hi - lo,
            "idle_by_span": dict(sorted(idle_by_span.items(),
                                        key=lambda kv: -kv[1])),
            "names": names, "handoff_s": handoffs(ours)}


def reduce_file(path: str) -> dict:
    return reduce(*read_events(path))


def latest_trace(root: Optional[Path] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``<root>/.bench_out/trace/``."""
    found = glob.glob(os.path.join(root or ROOT, ".bench_out", "trace",
                                   "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def of_run(trace: Optional[dict]) -> Optional[dict]:
    """The program spans of the run whose ``bench.trace`` reduction is
    ``trace``; None where there is none, or the newest trace file is not
    that run's."""
    if not trace or "window_s" not in trace:
        return None
    path = latest_trace()
    if path is None:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce_file(path)
    spans = _cache[key]
    if not math.isclose(spans["window_s"], trace["window_s"],
                        rel_tol=1e-9, abs_tol=0.0):
        return None
    return spans


def span_record(trace: Optional[dict], name: str) -> Optional[dict]:
    """The record of one program span name (``count``, ``seconds``,
    ``self_s``, ``idle_s``) in the run's trace; None where it has none."""
    spans = of_run(trace)
    return spans["names"].get(name) if spans else None


if __name__ == "__main__":
    json.dump(reduce_file(sys.argv[1]), sys.stdout, indent=1)
    print()

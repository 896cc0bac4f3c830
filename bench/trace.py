"""Profiler capture and the reduction from a trace to numbers.

A traced run records one ``jax.profiler`` trace around the measured window
and marks what the host does with the benchmark's own spans
(``TraceAnnotation`` names starting with ``bench.``). ``reduce`` then reads
the ``.xplane.pb`` with nothing but JAX:

- device ops: the events of the ``XLA Ops`` line of every device plane;
- busy time: the union of a device's op intervals inside the window,
  averaged over the devices; idle share = 1 - busy / window;
- op totals: device seconds summed per op (its HLO name, such as
  ``%fusion.12``), over all devices, counting only ops that hold no other
  op (a ``while`` and the ops of its body would count the same time
  twice);
- idle gaps: the stretches of the window in which device 0 ran nothing,
  each charged to the innermost ``bench.`` span that holds its middle
  (``host.other`` where none does).

The window is the ``bench.window`` span when the trace has one, else the
extent of the device ops.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


class Tracer:
    """Starts and stops one profiler trace; its spans cost nothing when the
    run is not traced."""

    def __init__(self, enabled: bool, directory: str):
        self.enabled = enabled
        self.directory = directory
        self._window = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def start(self) -> None:
        if not self.enabled:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> Optional[str]:
        """End the trace; returns the path of its ``.xplane.pb``."""
        if not self.enabled or self._window is None:
            return None
        import jax

        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            self.directory, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.directory}")
        return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[list] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that merged ``busy`` leaves uncovered."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def attribute(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    """The innermost (shortest) span that holds the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2
    inside = [(b - a, name) for name, a, b in spans if a <= mid < b]
    return min(inside)[1] if inside else "host.other"


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def leaves(events: list) -> list:
    """The events that hold no other event of the same line."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= e[2] or nxt[2] > e[2]]


def read_events(path: str):
    """(device ops by plane, host spans) of an ``.xplane.pb``, in seconds:
    ``{plane: [(op, start, end)]}`` and ``[(span, start, end)]``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (op_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return ops, spans


def reduce(ops: Dict[str, list], spans: list, top: int = 10) -> dict:
    """Busy and idle time, op totals and attributed idle gaps."""
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    all_ops = [(a, b) for events in ops.values() for _, a, b in events]
    if windows:
        lo, hi = windows[0]
    elif all_ops:
        lo, hi = min(a for a, _ in all_ops), max(b for _, b in all_ops)
    else:
        raise ValueError("trace has neither a window span nor device ops")
    window_s = hi - lo
    planes = sorted(ops)
    busy_by_plane = {p: union(clip([(a, b) for _, a, b in ops[p]], lo, hi))
                     for p in planes}
    busy_s = (sum(length(v) for v in busy_by_plane.values()) / len(planes)
              if planes else 0.0)

    totals: Dict[str, float] = defaultdict(float)
    for p in planes:
        for name, a, b in leaves(ops[p]):
            totals[name] += max(0.0, min(b, hi) - max(a, lo))
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    idle = gaps(busy_by_plane[planes[0]], lo, hi) if planes else [(lo, hi)]
    by_host: Dict[str, list] = defaultdict(lambda: [0.0, 0, 0.0])
    for g in idle:
        rec = by_host[attribute(g, inner)]
        rec[0] += g[1] - g[0]
        rec[1] += 1
        rec[2] = max(rec[2], g[1] - g[0])
    idle_gaps = [[f"{name} ({n} gaps, longest {longest * 1e3:.3f} ms)", total]
                 for name, (total, n, longest) in
                 sorted(by_host.items(), key=lambda kv: -kv[1][0])[:top]]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "devices": len(planes),
        "breakdown": {"device_ops": [[n, s] for n, s in device_ops],
                      "idle_gaps": idle_gaps},
    }


def reduce_file(path: str) -> dict:
    return reduce(*read_events(path))

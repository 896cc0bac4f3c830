"""Device time per named scope of the program, in a traced run.

The program names the parts of its layers with ``jax.named_scope``
(``mla.proj``, ``mla.core``, ``moe.router``, ``moe.experts``,
``moe.shared``); the compiler keeps the name path in each HLO op's
``op_name`` metadata. The profiler's device ops (the ``XLA Ops`` line)
carry the op's HLO name but not that metadata, so a traced run of the
cell's serving driver (``bench/drivers/serve_queue_mla_moe.py``) writes,
next to the trace, ``op_scopes.json``: for each program the run executed
(its HLO module name), the scope of each op, read from the compiled
program's text (``op_scopes``). A fusion carries its root op's name path, so it is
charged to its root op's scope: the innermost of ``SCOPES`` in that path.
The TPU compiler's grouped-product kernels (op names ``ragged-dot...``)
carry no name path; the program's only grouped products are the held
experts', so they are charged to ``moe.experts``.

A device op belongs to the program whose execution (the ``XLA Modules``
line) holds its start. This module gives, over the window, the device
seconds of the ops of each scope, counting only ops that hold no other op
(``bench.trace.leaves``) and averaging over the devices.

A reader gets the run's ``bench.trace`` reduction; ``of_run`` takes the
newest trace under ``.bench_out/trace/`` and accepts it only where its
window is the one that reduction measured (as ``bench.spans.of_run``).
To print the reduction of a trace by hand, with the ops of most device
time and their scopes::

    python3 -m bench.scopes <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import math
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Optional

from bench import spans as sp
from bench import trace as tr

SCOPES = ("mla.proj", "mla.core", "moe.router", "moe.experts", "moe.shared")
MAP_FILE = "op_scopes.json"
MODULES_LINE = "XLA Modules"
_PART = re.compile(r"[^/\s:\"'=,()]+")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')

_cache: Dict[tuple, dict] = {}


def scope_of(texts) -> Optional[str]:
    """The innermost of ``SCOPES`` in the first name path among ``texts``
    that names one."""
    for text in texts:
        if not isinstance(text, str):
            continue
        found = [p for p in _PART.findall(text) if p in SCOPES]
        if found:
            return found[-1]
    return None


def op_scopes(hlo_text: str):
    """(module name, ``{op name: scope}``) of a compiled program's text."""
    module = re.search(r"^HloModule ([^\s,]+)", hlo_text, re.M).group(1)
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, path = m.groups()
            scope = ("moe.experts" if path.startswith("ragged-dot")
                     else scope_of([path]))
            if scope:
                out[name] = scope
    return module, out


def write_map(trace_path: str, hlo_texts) -> None:
    """``op_scopes.json`` beside the trace, from the programs' texts."""
    maps = dict(op_scopes(t) for t in hlo_texts)
    with open(os.path.join(os.path.dirname(trace_path), MAP_FILE), "w") as f:
        json.dump(maps, f)


def _module_of(name: str, maps: dict) -> Optional[str]:
    """The program of ``maps`` that a ``XLA Modules`` event names."""
    return next((m for m in maps if name == m or name.startswith(m + "(")
                 or name.startswith(m + ".")), None)


def read_ops(path: str):
    """``{plane: [(scope or None, start, end, program, op)]}`` of the device
    ops of an ``.xplane.pb``, in seconds, and the window's host spans."""
    from jax.profiler import ProfileData

    map_path = os.path.join(os.path.dirname(path), MAP_FILE)
    maps = {}
    if os.path.exists(map_path):      # a run of a program with no scopes
        with open(map_path) as f:     # writes none
            maps = json.load(f)
    data = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            runs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module_of(e.name, maps))
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else []))
            starts = [r[0] for r in runs]
            if tr.OPS_LINE not in lines:
                continue
            found = ops.setdefault(plane.name, [])
            for e in lines[tr.OPS_LINE].events:
                op = tr.op_name(e.name).lstrip("%")
                k = bisect.bisect_right(starts, e.start_ns) - 1
                program = runs[k][2] if k >= 0 and e.start_ns < runs[k][1] \
                    else None
                scope = maps[program].get(op) if program else None
                found.append((scope, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9, program,
                              op))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name == tr.WINDOW_SPAN)
    return ops, spans


def _window(ops: Dict[str, list], spans: list):
    return sp.window({p: [e[:3] for e in ev] for p, ev in ops.items()}, spans)


def reduce(ops: Dict[str, list], spans: list) -> dict:
    """Window length and device seconds per scope (averaged over the
    devices)."""
    lo, hi = _window(ops, spans)
    seconds: Dict[str, float] = defaultdict(float)
    for events in ops.values():
        for scope, a, b, *_ in tr.leaves(events):
            if scope is not None:
                seconds[scope] += max(0.0, min(b, hi) - max(a, lo))
    n = max(len(ops), 1)
    return {"window_s": hi - lo,
            "seconds": {k: v / n for k, v in sorted(seconds.items())}}


def reduce_file(path: str) -> dict:
    return reduce(*read_ops(path))


def of_run(trace: Optional[dict]) -> Optional[dict]:
    """Seconds per scope of the run whose ``bench.trace`` reduction is
    ``trace``; None where there is none or the newest trace is another's."""
    if not trace or "window_s" not in trace:
        return None
    path = sp.latest_trace()
    if path is None:
        return None
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = reduce_file(path)
    found = _cache[key]
    if not math.isclose(found["window_s"], trace["window_s"], rel_tol=1e-9,
                        abs_tol=0.0):
        return None
    return found["seconds"]


def scope_seconds(trace: Optional[dict], scope: str) -> Optional[float]:
    """Device seconds of one scope's ops in the run's window; None where
    the trace names none of them."""
    seconds = of_run(trace)
    return seconds.get(scope) if seconds else None


def top_ops(path: str, n: int = 25) -> list:
    """The ``n`` ops of most device seconds in the window, as
    (program, op, scope, seconds)."""
    ops, spans = read_ops(path)
    lo, hi = _window(ops, spans)
    total: Dict[tuple, float] = defaultdict(float)
    for events in ops.values():
        for scope, a, b, program, op in tr.leaves(events):
            total[(program, op, scope)] += max(0.0, min(b, hi) - max(a, lo))
    return [[*k, v] for k, v in sorted(total.items(),
                                        key=lambda kv: -kv[1])[:n]]


if __name__ == "__main__":
    json.dump({"scopes": reduce_file(sys.argv[1]),
               "top_ops": top_ops(sys.argv[1])}, sys.stdout, indent=1)
    print()

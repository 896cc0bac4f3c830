"""A closed loop of one client running the GAP task graph.

The program under test is ``repro.tasks.graph.gap_task_graph(adj, w)
.run(scope)`` on one long-lived ``TaskScope`` of the configuration's
substrate: bfs, cc, pagerank, sssp and tc, then bc after bfs, then a
summary, every kernel ending in ``block_until_ready``. The client runs the
graph back to back; each run's wall time is one sample. The input graph is
made from the seed by the benchmark's copy of the Kronecker generator.

Traffic keys: ``warmup_graphs`` run in set-up (every kernel compiles
there), ``check_graphs`` run per seed by ``bench/control.py``,
``trace_seconds`` of the window traced in a traced run.

Check: every graph of the window is checked. Its answers are read back
to the host inside the graph (the summary task reads them), so the window
keeps each different answer, by its bytes, and drops the rest; once the
window has closed, each of those is compared with the float64 reference
(``bench/reference/gap.py``). Integer answers (bfs levels, components,
distances, triangles, the summary's counts) must be equal; pagerank and bc
are compared by their largest error relative to the reference's largest
value.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from bench import stats
from bench.drivers.common import Context, Outcome, device_now
from bench.reference import gap as ref_gap

EXACT = ("bfs", "cc", "sssp", "tc")
COUNTS = ("reached", "components", "finite_paths", "triangles")


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared for one graph's answers."""
    mismatches = sum(int(np.sum(np.asarray(got[k]) != np.asarray(ref[k])))
                     for k in EXACT)
    mismatches += sum(got["summary"][k] != ref["summary"][k] for k in COUNTS)
    pr_ref, bc_ref = ref["pagerank"], ref["bc"]
    pr_scale = np.max(np.abs(pr_ref))
    bc_scale = max(np.max(np.abs(bc_ref)), 1.0)
    pr_err = max(np.max(np.abs(np.asarray(got["pagerank"], np.float64)
                               - pr_ref)) / pr_scale,
                 abs(got["summary"]["pr_mass"] - pr_ref.sum()) / pr_ref.sum())
    bc_err = max(np.max(np.abs(np.asarray(got["bc"], np.float64) - bc_ref))
                 / bc_scale,
                 abs(got["summary"]["max_bc"] - bc_ref.max()) / bc_scale)
    return {"exact_mismatches": float(mismatches),
            "pagerank_rel_err": float(pr_err), "bc_rel_err": float(bc_err)}


def graph_input(c: dict, seed: int):
    return ref_gap.kronecker_graph(c["scale"], c["edge_factor"],
                                   tuple(c["initiator"]), seed,
                                   c["max_weight"])


def digest(res: dict) -> tuple:
    """Every answer of one graph, as bytes: graphs that answered alike
    share a digest, so each different answer is compared once."""
    return tuple((k, np.asarray(v).tobytes()) for k, v in sorted(
        res.items()) if k != "summary") + tuple(sorted(
            res["summary"].items()))


def worst_of(answers, ref: dict) -> dict:
    """The worst of each number compared over graphs' answers."""
    worst = {"exact_mismatches": 0.0, "pagerank_rel_err": 0.0,
             "bc_rel_err": 0.0}
    for res in answers:
        for k, v in compare(res, ref).items():
            worst[k] = max(worst[k], v)
    return worst


def run(ctx: Context) -> Outcome:
    from repro.tasks.api import TaskScope
    from repro.tasks.graph import gap_task_graph

    c, t = ctx.config, ctx.traffic
    adj_np, w_np = graph_input(c, ctx.seed)
    graph = gap_task_graph(jnp.asarray(adj_np), jnp.asarray(w_np),
                           c["source_node"])
    ctx.mark("graph input")
    distinct, times, failed = {}, [], 0
    scope = TaskScope(c["substrate"])
    try:
        for _ in range(t["warmup_graphs"]):
            graph.run(scope)
        ctx.mark("warm-up")
        ctx.tracer.start()
        tracing = True
        t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            with ctx.tracer.span("graph.run"):
                try:
                    res = graph.run(scope)
                except Exception:   # an answer that never comes is counted
                    res, failed = None, failed + 1
            b = time.perf_counter()
            times.append(b - a)
            if res is not None:     # each different answer, for the check
                distinct.setdefault(digest(res), res)
            if tracing and b - t0 >= t["trace_seconds"]:
                trace_path, traced_graphs = ctx.tracer.stop(), len(times)
                tracing = False
            if b - t0 >= ctx.seconds:
                break
        if tracing:
            trace_path, traced_graphs = ctx.tracer.stop(), len(times)
    finally:
        scope.close()
    device = device_now(ctx.devices)

    worst = worst_of(distinct.values(), ref_gap.gap_suite(adj_np, w_np, c))
    checks = [(k, v, ctx.limit(k)) for k, v in worst.items()]
    checks.append(("failed_graphs", float(failed), 0.0))
    return Outcome(
        attempted=len(times), failed=failed,
        end_to_end={"setup_s": t0 - ctx.t_start,
                    "graph_p95_ms": stats.nearest_rank(times, 95) * 1e3},
        facts={"graphs_traced": traced_graphs, "graphs": len(times),
               "graph_p50_ms": stats.nearest_rank(times, 50) * 1e3},
        checks=checks, device=device, trace_path=trace_path)

"""Benchmark harness — one section per paper figure plus the roofline table.

  fig1  — per-kernel speedup over serial for every scheduling strategy
          (paper Fig. 1: the seven-framework comparison)
  fig3  — Relic's per-kernel speedups (paper Fig. 3)
  fig4  — geomean speedup without negative outliers (paper Fig. 4 method:
          a kernel that degrades under a strategy contributes 1.0 — the
          developer would keep the serial version)
  spsc  — raw scheduling overhead: ns per submit+wait round-trip per
          structure (the mechanism behind the figures)
  wavefront — the GAP kernel task graph executed end-to-end over every
          substrate in the repro.core.schedulers registry via the
          repro.tasks.api.TaskGraph façade (dependency-aware scheduling,
          not just the two-task microbenchmark)
  grain — parallel_for grain-size sweep per substrate (grain is the
          paper's central variable: tasks-per-chunk vs scheduling
          overhead on a fixed GIL-releasing µs-scale body)
  paper — the headline table (paper §IV/§VII): speedup-over-serial for
          every ``repro.workloads`` workload × execution variant
          (paired, chunked) × substrate, each cell oracle-checked
          before it is timed
  scaling — the lane-scaling trajectory past the paper's SMT pair:
          relic-pool per-task overhead at lanes 1/2/4 against the
          single-lane relic pair (lanes=1 must not tax the pair), plus
          the chunked workloads striped over the lanes
  skew  — skew resistance: every workload under power-law task costs,
          chunked over small-ring self-rebalancing pools at lanes 2/4,
          speedup against the skewed serial baseline
  faults — robustness: the supervised pool's per-task cost and
          kill-a-lane detection latency / recovery time / throughput
          dip at lanes 2/4 with respawn, loss accounting asserted exact
          (no ``speedup=`` on these rows — they gate on invariants)
  roofline — summary of the dry-run artifacts, if present

Output: ``name,us_per_call,derived`` CSV per line on stdout (unchanged
format); ``--json PATH`` additionally writes the same rows, grouped per
section with run metadata, to a machine-readable JSON file (convention:
``BENCH_<tag>.json``) so the perf trajectory is recorded across PRs.
``--compare BENCH_old.json`` flags every row more than ``--compare-tol``
worse than the same-named row of an earlier file and exits non-zero —
the measured-trajectory gate (also non-zero when the baseline shares no
rows with the run: a vacuous gate fails loudly). ``--compare-metric us``
(default) gates on absolute µs — same host, same phase only;
``--compare-metric speedup`` gates on each row's recorded
speedup-over-serial, which cancels shared-host drift between recording
sessions (see compare_against). ``--only`` takes one section or a
comma-separated list (``--only paper,scaling``).
Usage: PYTHONPATH=src python -m benchmarks.run [--iters 1000]
       [--only paper,scaling] [--json BENCH_new.json]
       [--compare BENCH_old.json]
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

STRATEGIES = ["serial", "relic_spsc", "locked_queue_spin",
              "locked_queue_condvar", "threadpool_futures", "thread_per_task",
              "jax_async_stream", "fused_vmap"]


class Emitter:
    """Prints the historical CSV stream and collects rows for --json."""

    def __init__(self):
        self.sections: dict = {}

    def comment(self, text: str) -> None:
        print(f"# {text}")

    def header(self, text: str) -> None:
        self.comment(text)
        print("name,us_per_call,derived")

    def row(self, name: str, us: float, derived: str) -> None:
        print(f"{name},{us:.2f},{derived}")
        section = name.split("/", 1)[0]
        self.sections.setdefault(section, []).append(
            {"name": name, "us_per_call": round(us, 3), "derived": derived})

    def dump(self, path: str, meta: dict) -> None:
        payload = {"meta": meta, "sections": self.sections}
        Path(path).write_text(json.dumps(payload, indent=2, sort_keys=True))


def run_figures(iters: int, em: Emitter):
    from benchmarks.schedulers import bench_strategies
    from repro.workloads import PAPER_WORKLOADS, make_workload

    results = {}
    for name in PAPER_WORKLOADS:
        w = make_workload(name)
        task_a, task_b = w.tasks
        dispatch_a, dispatch_b = w.dispatches
        results[name] = bench_strategies(
            task_a, task_b, w.fused_task(),
            dispatch_a=dispatch_a, dispatch_b=dispatch_b, iters=iters)

    # fig1: µs/iter and speedup-over-serial per kernel × strategy
    em.header("fig1: per-kernel scheduling comparison")
    for kernel, res in results.items():
        base = res["serial"]
        for strat in STRATEGIES:
            sp = base / res[strat]
            em.row(f"fig1/{kernel}/{strat}", res[strat], f"speedup={sp:.3f}")

    # fig3: Relic per-kernel speedups
    em.header("fig3: Relic speedup over serial per kernel")
    for kernel, res in results.items():
        sp = res["serial"] / res["relic_spsc"]
        em.row(f"fig3/{kernel}", res["relic_spsc"], f"speedup={sp:.3f}")

    # fig4: geomean without negative outliers
    em.header("fig4: geomean speedup, negative outliers replaced by serial")
    fig4 = {}
    for strat in STRATEGIES:
        sps = [max(results[k]["serial"] / results[k][strat], 1.0)
               for k in results]
        gm = math.exp(sum(math.log(s) for s in sps) / len(sps))
        fig4[strat] = gm
        mean_us = sum(results[k][strat] for k in results) / len(results)
        em.row(f"fig4/{strat}", mean_us, f"geomean_speedup={gm:.3f}")
    best_other = max((v for k, v in fig4.items()
                      if k not in ("relic_spsc", "fused_vmap", "serial")),
                     default=1.0)
    rel = fig4.get("relic_spsc", 1.0)
    em.row("fig4/relic_vs_best_framework", 0.0,
           f"relic_gain={(rel / best_other - 1) * 100:.1f}%")
    return results


def run_spsc(iters: int, em: Emitter):
    """Raw round-trip overhead per scheduling structure (empty task)."""
    from benchmarks.schedulers import bench_strategies

    import jax
    import jax.numpy as jnp

    zero = jnp.zeros(())
    f = jax.jit(lambda x: x + 1)
    f(zero).block_until_ready()
    res = bench_strategies(lambda: f(zero), lambda: f(zero),
                           lambda: f(zero), iters=iters)
    em.header("spsc: scheduling overhead on a trivial task")
    for k, v in res.items():
        em.row(f"spsc/{k}", v, f"overhead_vs_serial={v - res['serial']:.2f}us")
    run_spsc_overhead(iters, em)
    return res


def run_spsc_overhead(iters: int, em: Emitter):
    """The per-task overhead table (ns per submit+wait round-trip): for each
    registered substrate, the raw-SPI single path (one submit() per task),
    the raw-SPI batch path (one submit_many() burst per window), and the
    façade path (one TaskHandle per task through TaskScope.submit). Empty
    Python task, so the number is pure scheduling cost — the floor the
    grain-size guidance in docs/EXPERIMENTS.md is derived from."""
    from repro.core.schedulers import available_schedulers, make_scheduler
    from repro.tasks.api import TaskScope

    window = 64                       # tasks per submit+wait window (< ring 128)
    reps = max(iters // 4, 25)        # windows per timed pass
    warmup = max(reps // 6, 5)
    rounds = 5                        # min over interleaved rounds (see below)

    def nop():
        pass

    batch_tasks = [(nop, (), {})] * window

    def time_variants(variants):
        """Time each named window-runner; returns {name: ns_per_task}.

        One *round* times every variant back-to-back, and the reported
        number is the min over rounds — so a noisy-neighbour phase (this
        is a shared container) degrades all variants of a round together
        instead of skewing their comparison."""
        best = {k: float("inf") for k in variants}
        for _ in range(rounds):
            for key, run_window in variants.items():
                for _ in range(warmup):
                    run_window()
                t0 = time.perf_counter()
                for _ in range(reps):
                    run_window()
                ns = (time.perf_counter() - t0) / (reps * window) * 1e9
                best[key] = min(best[key], ns)
        return best

    em.header("spsc/overhead: ns per submit+wait round-trip "
              f"(empty task, window={window})")
    for name in available_schedulers():
        with make_scheduler(name) as sched:
            def spi_single(sched=sched):
                for _ in range(window):
                    sched.submit(nop)
                sched.wait()

            def spi_batch(sched=sched):
                sched.submit_many(batch_tasks)
                sched.wait()

            spi = time_variants({"single": spi_single, "batch": spi_batch})
        with TaskScope(name) as scope:
            def facade(scope=scope):
                for _ in range(window):
                    scope.submit(nop)
                scope.barrier()

            ns_facade = time_variants({"facade": facade})["facade"]
        ns_single, ns_batch = spi["single"], spi["batch"]
        em.row(f"spsc/overhead/{name}/single", ns_single / 1e3,
               f"ns_per_task={ns_single:.0f}")
        em.row(f"spsc/overhead/{name}/batch", ns_batch / 1e3,
               f"ns_per_task={ns_batch:.0f}"
               f";batch_vs_single={ns_batch / ns_single - 1:+.1%}")
        em.row(f"spsc/facade/{name}", ns_facade / 1e3,
               f"ns_per_task={ns_facade:.0f}")


def run_wavefront(iters: int, em: Emitter):
    """GAP task graph over every registered substrate (same TaskGraph, same
    dependency structure — only the scheduling substrate varies)."""
    from repro.core.schedulers import available_schedulers
    from repro.tasks.api import TaskScope
    from repro.tasks.graph import gap_task_graph, kronecker_graph

    adj, w = kronecker_graph()
    graph = gap_task_graph(adj, w)
    # compile/warm every kernel once outside the timed region
    baseline = graph.run("serial")

    iters = max(iters // 10, 10)
    em.header("wavefront: GAP task graph per substrate (µs per full graph)")
    times = {}
    for name in available_schedulers():
        with TaskScope(name) as scope:
            t0 = time.perf_counter()
            for _ in range(iters):
                res = graph.run(scope)
            us = (time.perf_counter() - t0) / iters * 1e6
        assert res["summary"] == baseline["summary"], name
        times[name] = us
    for name, us in times.items():
        sp = times["serial"] / us
        em.row(f"wavefront/{name}", us, f"speedup={sp:.3f}")
    return times


def run_grain(iters: int, em: Emitter):
    """parallel_for grain-size sweep: n=256 instances of a GIL-releasing
    µs-scale NumPy body, chunked at each grain, per substrate. Grain is
    the paper's central variable — too fine and scheduling overhead
    dominates (one task per index), too coarse and there is nothing left
    to overlap (one task total)."""
    import numpy as np

    from repro.core.schedulers import available_schedulers
    from repro.tasks.api import TaskScope, parallel_for

    n = 256
    grains = [1, 8, 32, 128, 256]
    rng = np.random.default_rng(0)
    m = rng.standard_normal((48, 48)).astype(np.float32)

    def body(_i):
        np.dot(m, m)  # ~µs-scale, releases the GIL (paper §IV task sizes)

    reps = max(iters // 30, 5)
    times: dict = {}
    for name in available_schedulers():
        times[name] = {}
        with TaskScope(name) as scope:
            for grain in grains:
                parallel_for(scope, n, body, grain=grain)  # warmup
                t0 = time.perf_counter()
                for _ in range(reps):
                    parallel_for(scope, n, body, grain=grain)
                times[name][grain] = (time.perf_counter() - t0) / reps * 1e6
    em.header(f"grain: parallel_for grain sweep, n={n} µs-scale bodies "
              "(µs per loop)")
    for name, per_grain in times.items():
        for grain, us in per_grain.items():
            sp = times["serial"][grain] / us
            em.row(f"grain/{name}/g{grain}", us,
                   f"tasks={math.ceil(n / grain)};speedup={sp:.3f}")
    return times


def run_paper(iters: int, em: Emitter):
    """The paper's headline table: speedup-over-serial for every workload ×
    execution variant × substrate.

    Rows: ``paper/<workload>/serial`` (the per-workload baseline, µs per
    run of all instances) and ``paper/<workload>/<variant>/<substrate>``
    for variant ∈ {paired, chunked} × substrate ∈ every registered
    non-serial substrate. Each variant × substrate cell is oracle-checked
    once (outside the timed region) before it is timed; ``oracle=ok`` in
    the derived column records that the numbers come from verified runs.
    Cells are timed as noise floors (min over short rounds via
    :func:`timeit_us_floor`) and the whole table is measured in several
    **full passes** with per-row minima across passes: one cell's floor
    samples then span the entire section's wall-clock (minutes) instead
    of one contiguous ~50 ms window, so a noise burst on the shared host
    can no longer condemn whichever cell it happened to land on. The
    recorded trajectory tracks the host's quiet-window floor — the number
    that reproduces across runs — not the phase a single mean lands in.
    """
    from benchmarks.schedulers import timeit_us_floor
    from repro.core.schedulers import available_schedulers
    from repro.tasks.api import TaskScope
    from repro.workloads import available_workloads, make_workload

    passes = 3
    reps = max(iters // 15, 9)        # per pass; floors span passes too
    warmup = max(reps // 5, 3)
    # Skip serial (it is every row's baseline) and "relic-pool" (identical
    # to the self-describing relic2 convenience name at its default
    # lanes=2 — timing both would re-measure one config twice).
    substrates = [n for n in available_schedulers()
                  if n not in ("serial", "relic-pool")]

    def timeit(run) -> float:
        return timeit_us_floor(run, reps, warmup, rounds=3)

    workloads = {name: make_workload(name) for name in available_workloads()}
    floor: dict = {}
    speedup: dict = {}
    for p in range(passes):
        for wname, w in workloads.items():
            if p == 0:
                w.check(w.serial())            # builds, warms, verifies
            us_serial_p = timeit(w.serial)
            key = f"paper/{wname}/serial"
            floor[key] = min(floor.get(key, float("inf")), us_serial_p)
            for sub in substrates:
                with TaskScope(sub) as scope:
                    for variant, run in (
                            ("paired", lambda: w.paired(scope)),
                            ("chunked", lambda: w.chunked(scope, grain=1))):
                        if p == 0:
                            w.check(run())     # verified before timing
                        key = f"paper/{wname}/{variant}/{sub}"
                        us_p = timeit(run)
                        floor[key] = min(floor.get(key, float("inf")), us_p)
                        # Speedup is paired WITHIN the pass (this pass's
                        # serial vs this pass's cell — near-same host
                        # phase), best pass kept: a serial floor caught
                        # in a deep quiet window must not deflate every
                        # cell's speedup measured in louder ones.
                        speedup[key] = max(speedup.get(key, 0.0),
                                           us_serial_p / us_p)

    em.header("paper: workload speedup over serial "
              "(µs per all-instances run; oracle-checked; "
              f"floors + best same-pass speedups over {passes} passes)")
    for wname, w in workloads.items():
        em.row(f"paper/{wname}/serial", floor[f"paper/{wname}/serial"],
               f"n={w.n_instances};speedup=1.000;oracle=ok")
        for sub in substrates:
            for variant in ("paired", "chunked"):
                key = f"paper/{wname}/{variant}/{sub}"
                em.row(key, floor[key],
                       f"speedup={speedup[key]:.3f};oracle=ok")


def run_scaling(iters: int, em: Emitter):
    """The lane-scaling trajectory: what RelicPool costs and buys past the
    paper's SMT pair.

    Overhead rows (``scaling/overhead/<config>/{single,batch}``, empty
    Python task, ns per submit+wait round-trip): the single-lane ``relic``
    pair as the in-run reference, then ``relic-pool`` at lanes 1/2/4. The
    derived column carries ``vs_relic`` for the pool configs — lanes=1 is
    the price of the striping bookkeeping alone and must stay within a few
    percent of the pair (scaling must not tax the pair). Every config is
    timed in interleaved rounds (one round visits every config, min over
    rounds), so a noisy-neighbour phase degrades a whole round together
    instead of skewing the lanes-vs-pair comparison.

    Chunked-workload rows (``scaling/chunked/<workload>/...``): every
    ``repro.workloads`` workload at 8 instances, worksharing-chunked at
    grain=1 over lanes 1/2/4, oracle-checked before timing, with the
    workload's serial run as the per-row baseline.
    """
    from benchmarks.schedulers import timeit_us_floor
    from repro.core.schedulers import make_scheduler
    from repro.tasks.api import TaskScope
    from repro.workloads import available_workloads, make_workload

    window = 64                       # tasks per submit+wait window (< ring 128)
    reps = max(iters // 16, 15)
    warmup = max(reps // 6, 5)
    rounds = 16                       # many short rounds (vs spsc/overhead's 5
    lane_counts = [1, 2, 4]           # long ones): the min is a cross-config
                                      # comparison, and floors converge with
                                      # round count, not round length

    def nop():
        pass

    batch_tasks = [(nop, (), {})] * window
    configs = [("relic", "relic", {})] + [
        (f"lanes{n}", "relic-pool", {"lanes": n}) for n in lane_counts]

    best = {(label, var): float("inf")
            for label, _, _ in configs for var in ("single", "batch")}
    for rnd in range(rounds):
        # Alternate visiting order so slow drift on the shared host cannot
        # systematically favour whichever config runs first.
        for label, name, kwargs in (configs if rnd % 2 == 0
                                    else configs[::-1]):
            # One substrate alive at a time: an idle pool's spinning
            # assistants would steal cycles from the config being timed.
            with make_scheduler(name, **kwargs) as sched:
                def single(sched=sched):
                    for _ in range(window):
                        sched.submit(nop)
                    sched.wait()

                def batch(sched=sched):
                    sched.submit_many(batch_tasks)
                    sched.wait()

                for var, run_window in (("single", single), ("batch", batch)):
                    for _ in range(warmup):
                        run_window()
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        run_window()
                    ns = (time.perf_counter() - t0) / (reps * window) * 1e9
                    key = (label, var)
                    best[key] = min(best[key], ns)

    em.header("scaling/overhead: ns per submit+wait round-trip, relic-pool "
              f"lanes 1/2/4 vs the relic pair (empty task, window={window})")
    for label, _, _ in configs:
        for var in ("single", "batch"):
            ns = best[(label, var)]
            derived = f"ns_per_task={ns:.0f}"
            if label != "relic":
                ref = best[("relic", var)]
                derived += f";vs_relic={ns / ref - 1:+.1%}"
            em.row(f"scaling/overhead/{label}/{var}", ns / 1e3, derived)

    n_instances = 8                   # enough instances for 4 lanes + producer
    reps_w = max(iters // 10, 10)
    warmup_w = max(reps_w // 5, 3)
    em.header("scaling/chunked: workloads worksharing-chunked over N lanes "
              f"(µs per all-instances run, n={n_instances}, grain=1; "
              "oracle-checked)")
    for wname in available_workloads():
        w = make_workload(wname, n_instances=n_instances)
        w.check(w.serial())            # builds, warms, verifies
        us_serial = timeit_us_floor(w.serial, reps_w, warmup_w)
        em.row(f"scaling/chunked/{wname}/serial", us_serial,
               f"n={n_instances};speedup=1.000;oracle=ok")
        for lanes in lane_counts:
            with TaskScope("relic-pool", lanes=lanes) as scope:
                def run(w=w, scope=scope):
                    return w.chunked(scope, grain=1)

                w.check(run())         # verified before timing
                us = timeit_us_floor(run, reps_w, warmup_w)
                em.row(f"scaling/chunked/{wname}/lanes{lanes}", us,
                       f"speedup={us_serial / us:.3f};oracle=ok")


def run_skew(iters: int, em: Emitter):
    """Skew resistance: every workload under a power-law task-cost
    profile (``skew=1.0``: heaviest instance repeats its kernel n times,
    rank r ~ r**-1 of that), worksharing-chunked at grain=1 over a
    deliberately small-ring pool (capacity=4, n=16 instances — so burst
    remainders exist and the sweep actually runs; the pool re-stripes
    them and hands work to idle lanes) at lanes 2 and 4.

    Rows: ``skew/<workload>/serial`` (the skewed serial baseline) and
    ``skew/<workload>/lanes<N>``, each oracle-checked before timing, with
    the speedup over the serial baseline. Same measurement discipline as
    the paper table: noise-floor timing, several full passes, speedups
    paired within a pass, best pass kept.
    """
    from benchmarks.schedulers import timeit_us_floor
    from repro.core.schedulers import make_scheduler
    from repro.tasks.api import TaskScope
    from repro.workloads import available_workloads, make_workload

    passes = 3
    reps = max(iters // 20, 8)
    warmup = max(reps // 5, 3)
    capacity = 4                      # small rings: force remainder sweeps
    n_instances = 16
    skew = 1.0
    lane_counts = [2, 4]

    workloads = {name: make_workload(name, n_instances=n_instances,
                                     skew=skew)
                 for name in available_workloads()}
    floor: dict = {}
    speedup: dict = {}
    for p in range(passes):
        for wname, w in workloads.items():
            if p == 0:
                w.check(w.serial())            # builds, warms, verifies
            us_serial_p = timeit_us_floor(w.serial, reps, warmup, rounds=3)
            key = f"skew/{wname}/serial"
            floor[key] = min(floor.get(key, float("inf")), us_serial_p)
            for lanes in lane_counts:
                sched = make_scheduler("relic-pool", lanes=lanes,
                                       capacity=capacity)
                with TaskScope(sched) as scope:
                    def run(w=w, scope=scope):
                        return w.chunked(scope, grain=1)

                    if p == 0:
                        w.check(run())         # verified before timing
                    key = f"skew/{wname}/lanes{lanes}"
                    us_p = timeit_us_floor(run, reps, warmup, rounds=3)
                    floor[key] = min(floor.get(key, float("inf")), us_p)
                    speedup[key] = max(speedup.get(key, 0.0),
                                       us_serial_p / us_p)

    em.header("skew: power-law task costs over self-rebalancing pools "
              f"(chunked grain=1, n={n_instances}, skew={skew}, "
              f"capacity={capacity}; oracle-checked; floors + best "
              f"same-pass speedups over {passes} passes)")
    for wname, w in workloads.items():
        em.row(f"skew/{wname}/serial", floor[f"skew/{wname}/serial"],
               f"n={n_instances};skew={skew};speedup=1.000;oracle=ok")
        for lanes in lane_counts:
            key = f"skew/{wname}/lanes{lanes}"
            em.row(key, floor[key], f"speedup={speedup[key]:.3f};oracle=ok")


def run_stream(iters: int, em: Emitter):
    """The streaming-dataflow A/B (PR 9): stencil time-steps expressed
    three ways over the same work —

    * ``stream/stencil_steps/wavefront/lanes<N>`` — a TaskGraph of G
      independent 8-sweep chains (one node per single sweep) run in
      **barriered wavefronts** (``streaming=False``, the PR 6 baseline);
    * ``stream/stencil_steps/streaming/lanes<N>`` — the *same graph, same
      scope, same pass* with ``streaming=True``: tasks launch the moment
      their deps resolve, no global barrier. Carries ``vs_wavefront``
      (same-pass paired ratio, best pass kept) — the headline: positive at
      lanes ≥ 2 means dataflow overlap beat lockstep wavefronts;
    * ``stream/stencil_steps/pipeline/stages4`` — the ``streamed()``
      variant: a persistent 4-stage sweep-group :class:`Pipeline`, grids
      flowing through; plus ``.../chunked/lanes<N>`` (the PR 5
      worksharing shape) and a whole-instance ``Farm`` row for scale.

    ``stream/json_chunks/*`` reruns the shape on the byte-chunk jsondoc
    stream (stateless classify → stateful scan — work a barriered model
    cannot phrase at all, since the scan carry crosses chunk boundaries).
    Everything is oracle-checked on pass 0 before timing; floors +
    same-pass speedup discipline as the paper table.
    """
    import jax
    import numpy as np

    from benchmarks.schedulers import timeit_us_floor
    from repro.core.schedulers import make_scheduler
    from repro.stream import Farm, Pipeline
    from repro.tasks.api import TaskGraph, TaskScope
    from repro.workloads import make_workload
    from repro.workloads.stencil import SWEEPS, _np_stencil, stencil_sweep

    passes = 3
    reps = max(iters // 50, 4)
    warmup = max(reps // 4, 2)
    n_grids = 8
    lane_counts = [1, 2, 4]

    ws = make_workload("stencil", n_instances=n_grids)
    wj = make_workload("json", n_instances=n_grids)

    # -- the time-step graph: G independent chains of single-sweep nodes --
    def one_sweep(g):
        return jax.block_until_ready(stencil_sweep(g, sweeps=1))

    grids, _ = ws._stream_stages(stages=SWEEPS)   # G fresh grids, warmed
    jax.block_until_ready(stencil_sweep(grids[0], sweeps=1))  # warm 1-sweep
    graph = TaskGraph()
    tails = []
    for i, grid in enumerate(grids):
        prev = None
        for s in range(SWEEPS):
            node = f"g{i}s{s}"
            if prev is None:
                graph.task(node, lambda grid=grid: one_sweep(grid))
            else:
                graph.task(node, one_sweep, deps=(prev,))
            prev = node
        tails.append(prev)

    def check_graph():
        want = _np_stencil(ws._input())
        for tail in tails:
            np.testing.assert_allclose(
                np.asarray(graph.handle(tail).result()), want,
                rtol=1e-5, atol=1e-6)

    # -- persistent streamed pipelines (built once, reps flow through) ----
    s_items, s_fns = ws._stream_stages()                # 4 sweep-group stages
    j_items, j_fns = wj._stream_stages()                # classify -> scan
    stencil_pipe = Pipeline(list(s_fns), capacity=16).start()
    json_pipe = Pipeline(list(j_fns), capacity=32).start()
    farm_pipe = Pipeline(
        [Farm(lambda g: jax.block_until_ready(stencil_sweep(g)), workers=2,
              name="stencil-farm", capacity=16)], capacity=16).start()
    # Park every persistent network outside its own timing window: an idle
    # stage spin-waits on its input ring, and three spinning networks
    # contending for the GIL would tax every *other* row's measurement.
    for _pipe in (stencil_pipe, json_pipe, farm_pipe):
        _pipe.pause()

    floor: dict = {}
    speedup: dict = {}
    vs_wave: dict = {}
    try:
        with TaskScope(make_scheduler("serial")) as serial_scope:
            for p in range(passes):
                # serial baseline: the same graph, inline wavefronts
                if p == 0:
                    graph.run(serial_scope)
                    check_graph()
                us_serial = timeit_us_floor(
                    lambda: graph.run(serial_scope), reps, warmup, rounds=3)
                key = "stream/stencil_steps/serial"
                floor[key] = min(floor.get(key, float("inf")), us_serial)

                for lanes in lane_counts:
                    sched = make_scheduler("relic-pool", lanes=lanes)
                    with TaskScope(sched) as scope:
                        def run_wave(scope=scope):
                            return graph.run(scope, streaming=False)

                        def run_streaming(scope=scope):
                            return graph.run(scope, streaming=True)

                        if p == 0:
                            run_wave()
                            check_graph()
                            run_streaming()
                            check_graph()
                        us_w = timeit_us_floor(run_wave, reps, warmup,
                                               rounds=3)
                        us_s = timeit_us_floor(run_streaming, reps, warmup,
                                               rounds=3)
                        kw = f"stream/stencil_steps/wavefront/lanes{lanes}"
                        ks = f"stream/stencil_steps/streaming/lanes{lanes}"
                        floor[kw] = min(floor.get(kw, float("inf")), us_w)
                        floor[ks] = min(floor.get(ks, float("inf")), us_s)
                        speedup[kw] = max(speedup.get(kw, 0.0),
                                          us_serial / us_w)
                        speedup[ks] = max(speedup.get(ks, 0.0),
                                          us_serial / us_s)
                        vs_wave[ks] = max(vs_wave.get(ks, -1.0),
                                          us_w / us_s)  # same-pass pairing

                        def run_chunked(scope=scope):
                            return ws.chunked(scope, grain=1)

                        if p == 0:
                            ws.check(run_chunked())
                        kc = f"stream/stencil_steps/chunked/lanes{lanes}"
                        us_c = timeit_us_floor(run_chunked, reps, warmup,
                                               rounds=3)
                        floor[kc] = min(floor.get(kc, float("inf")), us_c)
                        speedup[kc] = max(speedup.get(kc, 0.0),
                                          us_serial / us_c)

                # streamed() pipeline + farm rows (persistent networks)
                for key, pipe, items, check in (
                        (f"stream/stencil_steps/pipeline/stages{len(s_fns)}",
                         stencil_pipe, s_items,
                         lambda out: ws.check(ws._stream_collect(out))),
                        ("stream/stencil_steps/farm/workers2",
                         farm_pipe, list(grids),
                         lambda out: [np.testing.assert_allclose(
                             np.asarray(o), _np_stencil(ws._input()),
                             rtol=1e-5, atol=1e-6) for o in out])):
                    pipe.resume()
                    if p == 0:
                        check(pipe.run(items))
                    us_p = timeit_us_floor(lambda: pipe.run(items),
                                           reps, warmup, rounds=3)
                    pipe.pause()
                    floor[key] = min(floor.get(key, float("inf")), us_p)
                    speedup[key] = max(speedup.get(key, 0.0),
                                       us_serial / us_p)

                # the jsondoc byte-chunk stream
                if p == 0:
                    wj.check(wj.serial())
                us_js = timeit_us_floor(wj.serial, reps, warmup, rounds=3)
                key = "stream/json_chunks/serial"
                floor[key] = min(floor.get(key, float("inf")), us_js)
                json_pipe.resume()
                if p == 0:
                    wj.check(wj._stream_collect(json_pipe.run(j_items)))
                us_jp = timeit_us_floor(lambda: json_pipe.run(j_items),
                                        reps, warmup, rounds=3)
                json_pipe.pause()
                key = "stream/json_chunks/pipeline"
                floor[key] = min(floor.get(key, float("inf")), us_jp)
                speedup[key] = max(speedup.get(key, 0.0), us_js / us_jp)
    finally:
        stencil_pipe.close()
        json_pipe.close()
        farm_pipe.close()

    em.header("stream: dataflow streaming vs barriered wavefronts "
              f"(stencil: {n_grids} grids x {SWEEPS} single-sweep chained "
              f"tasks, same graph/scope A-B; json: byte-chunk "
              f"classify->scan; oracle-checked; floors + best same-pass "
              f"speedups over {passes} passes)")
    em.row("stream/stencil_steps/serial", floor["stream/stencil_steps/serial"],
           f"n={n_grids};sweeps={SWEEPS};speedup=1.000;oracle=ok")
    for lanes in lane_counts:
        kw = f"stream/stencil_steps/wavefront/lanes{lanes}"
        ks = f"stream/stencil_steps/streaming/lanes{lanes}"
        kc = f"stream/stencil_steps/chunked/lanes{lanes}"
        em.row(kw, floor[kw], f"speedup={speedup[kw]:.3f};oracle=ok")
        em.row(ks, floor[ks], f"speedup={speedup[ks]:.3f};oracle=ok;"
                              f"vs_wavefront={vs_wave[ks] - 1:+.1%}")
        em.row(kc, floor[kc], f"speedup={speedup[kc]:.3f};oracle=ok")
    for key in (f"stream/stencil_steps/pipeline/stages{len(s_fns)}",
                "stream/stencil_steps/farm/workers2"):
        em.row(key, floor[key], f"speedup={speedup[key]:.3f};oracle=ok")
    em.row("stream/json_chunks/serial", floor["stream/json_chunks/serial"],
           f"n={n_grids};chunk={wj.stream_chunk};speedup=1.000;oracle=ok")
    em.row("stream/json_chunks/pipeline", floor["stream/json_chunks/pipeline"],
           f"speedup={speedup['stream/json_chunks/pipeline']:.3f};oracle=ok")


def load_baseline(path: str) -> dict:
    """Read and validate a --compare baseline BENCH file. Called *before*
    the benchmark sections run, so a missing/corrupt path fails in
    milliseconds instead of after minutes of timing."""
    payload = json.loads(Path(path).read_text())
    if not isinstance(payload.get("sections"), dict):
        raise SystemExit(f"--compare {path}: not a BENCH file (no sections)")
    return payload


_SPEEDUP_RE = re.compile(r"(?:^|;)speedup=([0-9.]+)")


def _row_speedup(row: dict):
    m = _SPEEDUP_RE.search(row.get("derived", ""))
    return float(m.group(1)) if m else None


def compare_against(em: Emitter, baseline: dict, tol: float,
                    label: str = "baseline", metric: str = "us"):
    """The measured-trajectory gate: flag every row of this run that is
    more than ``tol`` worse than the same-named row of an earlier BENCH
    payload. Returns ``(compared, regressions)``; callers exit non-zero
    on any regression — and on ``compared == 0``, because a gate whose
    baseline shares no rows with the run (wrong file, wrong --only
    section) is vacuous and must fail loudly, not pass silently.

    ``metric`` picks what "worse" means:

    * ``us`` — absolute µs per call. Right when baseline and run come
      from the same host *phase*; on a shared container whose effective
      CPU drifts between recordings, every row inherits the drift.
    * ``speedup`` — the row's recorded speedup-over-serial (parsed from
      the derived column; rows without one on both sides are skipped).
      Serial baselines are scheduling-free and code-stable, so host
      drift cancels and what remains is the scheduling layer's own
      trajectory — the paper's metric, and the right gate across
      recording sessions (compare µs only within one).
    """
    old = {r["name"]: r
           for rows in baseline.get("sections", {}).values() for r in rows}
    fingerprint = {k: baseline.get("meta", {}).get(k)
                   for k in ("cpu_count", "spin_pause_every", "python")}
    regressions = []
    compared = 0
    for rows in em.sections.values():
        for r in rows:
            b = old.get(r["name"])
            if b is None:
                continue
            if metric == "speedup":
                new_sp, base_sp = _row_speedup(r), _row_speedup(b)
                if new_sp is None or base_sp is None or base_sp <= 0:
                    continue
                compared += 1
                # >1: lost speedup vs baseline. A collapsed cell whose
                # speedup rounds to 0.000 must fail the gate loudly, not
                # fall out of the comparison — clamp instead of skip.
                ratio = base_sp / max(new_sp, 1e-9)
                if ratio > 1.0 + tol:
                    regressions.append({
                        "name": r["name"], "baseline_speedup": base_sp,
                        "speedup": new_sp, "ratio": round(ratio, 3)})
                continue
            base = b["us_per_call"]
            if base <= 0 or r["us_per_call"] <= 0:
                continue
            compared += 1
            ratio = r["us_per_call"] / base
            if ratio > 1.0 + tol:
                regressions.append({
                    "name": r["name"], "baseline_us": base,
                    "us": r["us_per_call"], "ratio": round(ratio, 3)})
    em.comment(f"compare: {compared} shared rows vs {label} "
               f"(metric {metric}, tol +{tol:.0%}, "
               f"baseline fingerprint {fingerprint})")
    for reg in regressions:
        if "speedup" in reg:
            em.comment(f"REGRESSION {reg['name']}: speedup "
                       f"{reg['baseline_speedup']:.3f} -> "
                       f"{reg['speedup']:.3f} (x{reg['ratio']:.2f})")
        else:
            em.comment(f"REGRESSION {reg['name']}: "
                       f"{reg['baseline_us']:.2f}us -> "
                       f"{reg['us']:.2f}us (x{reg['ratio']:.2f})")
    if compared == 0:
        em.comment("compare: FAILED — baseline shares no rows with this run "
                   "(wrong file or wrong --only section?)")
    elif not regressions:
        em.comment("compare: no per-row regressions")
    return compared, regressions


def run_serve(iters: int, em: Emitter):
    """Latency under load: the ``repro.serve`` subsystem measured as
    latency percentiles + throughput vs offered load.

    For each workload × lanes 1/2/4, three load points:

    * ``closed`` — 2 closed-loop clients (submit → wait → repeat, block
      admission): best-case latency and the saturation throughput that
      anchors the open-loop rates.
    * ``open50`` / ``open90`` — one open-loop client on a seeded Poisson
      schedule at 50% / 90% of the measured closed-loop throughput
      (reject admission, so overload is counted, not silently queued),
      with a generous deadline so SLO-miss accounting is exercised.

    Served work is drawn from the oracle-checked ``repro.workloads``
    registry and **every** completed response's value is verified with the
    workload's ``check_one`` oracle before the point's numbers are
    emitted — ``oracle=ok`` in the derived column means every latency
    sample comes from a correct response. Percentiles are the subsystem's
    own nearest-rank implementation (pinned against numpy by
    tests/test_serve.py). Rows carry no ``speedup=`` field: latency-vs-load
    is a new axis, gated by its own floors, not by speedup-over-serial.
    """
    from repro.runtime.config import resolve_serve_config
    from repro.serve import (
        STATUS_OK, ServeScheduler, percentiles, run_closed_loop,
        run_open_loop)
    from repro.workloads import make_workload

    lane_counts = [1, 2, 4]
    wl_names = ("histogram", "json")
    per_client = max(iters // 10, 15)      # closed-loop requests per client
    clients = 2
    n_open = max(iters // 5, 30)           # open-loop requests per point
    deadline_s = 0.25                      # generous: exercised, rarely missed

    def check_all(w, responses):
        """Oracle-check every completed-ok response; returns (ok, missed)."""
        ok = missed = 0
        for resp in responses:
            if resp.status == STATUS_OK:
                w.check_one(resp.value)
                ok += 1
            elif resp.status == "deadline_exceeded":
                missed += 1
            else:
                raise AssertionError(
                    f"serve bench response ended {resp.status}: {resp.error}")
        return ok, missed

    def latency_derived(responses):
        lats = [r.latency for r in responses if r.latency is not None]
        p = percentiles(lats)
        return p, (f"p50={p[50] * 1e6:.0f}us;p95={p[95] * 1e6:.0f}us;"
                   f"p99={p[99] * 1e6:.0f}us")

    em.header("serve: latency percentiles + throughput vs offered load "
              f"(closed {clients}x{per_client} reqs, open {n_open} reqs "
              "at 50%/90% of closed tput; every response oracle-checked)")
    for wname in wl_names:
        w = make_workload(wname)
        w.check(w.serial())                # builds, warms, verifies oracle
        tasks = w.tasks
        idx = [0]

        def work(tasks=tasks, idx=idx):
            fn = tasks[idx[0] % len(tasks)]
            idx[0] += 1
            return fn, ()

        for lanes in lane_counts:
            # Closed loop: block admission, no deadline — saturation point.
            cfg = resolve_serve_config(admission="block")
            with ServeScheduler(lanes=lanes, config=cfg) as server:
                res = run_closed_loop(
                    server, work, clients=clients,
                    requests_per_client=per_client)
                ok, _ = check_all(w, res.responses)
                stats = server.stats()
            tput = stats["throughput_rps"]
            p, derived = latency_derived(res.responses)
            em.row(f"serve/{wname}/lanes{lanes}/closed", p[50] * 1e6,
                   f"{derived};tput_rps={tput:.0f};n={ok};oracle=ok")

            # Open loop at 50% and 90% of the measured closed throughput:
            # reject admission + deadline, seeded Poisson schedule.
            for tag, frac in (("open50", 0.5), ("open90", 0.9)):
                rate = max(tput * frac, 1.0)
                cfg = resolve_serve_config(admission="reject")
                with ServeScheduler(lanes=lanes, config=cfg) as server:
                    res = run_open_loop(
                        server, work, rate_rps=rate, n_requests=n_open,
                        seed=lanes * 100 + int(frac * 100),
                        deadline_s=deadline_s)
                    ok, missed = check_all(w, res.responses)
                    stats = server.stats()
                p, derived = latency_derived(res.responses)
                em.row(
                    f"serve/{wname}/lanes{lanes}/{tag}", p[50] * 1e6,
                    f"{derived};offered_rps={rate:.0f};"
                    f"tput_rps={stats['throughput_rps']:.0f};n={ok};"
                    f"slo_miss={missed};rejected={res.rejected};oracle=ok")


def run_faults(iters: int, em: Emitter):
    """Robustness under injected faults: what a dead lane costs.

    Two measurements, rows carry no ``speedup=`` (robustness is a new
    axis, not a speedup claim — the gate for these rows is the asserted
    loss accounting, not a trajectory ratio):

    * ``faults/overhead`` — the per-task cost of the supervised pool:
      submit_batch+wait of no-op bursts through a 2-lane pool (liveness
      probes every 1024 producer spins, heartbeat bookkeeping on
      check_lanes).
    * ``faults/kill/lanesN`` — kill-a-lane (lanes 2 and 4, respawn on):
      a seeded KillSwitch takes lane 1 down with its first burst
      in-flight. Measured: detection latency (death -> check_lanes
      reporting the quarantine), recovery time (detection -> survivors
      drained + replacement lane live), and the throughput dip (wall
      time of the faulted run over a clean same-shape run). The lost
      count is asserted to equal the dead ring's in-flight count exactly
      (submitted - completed at death) and the pool ledger to balance —
      a violated invariant crashes the benchmark rather than emitting a
      row.
    * ``faults/stage_kill/workersN`` — the PR 10 stream stratum: a
      StageKillSwitch takes a farm worker down mid-stream with items in
      flight; ``Farm(respawn=True)`` quarantines, respawns, and re-emits
      exactly the lost tags. Measured: detection latency (loop death ->
      collector recovery entry), recovery time (recovery entry -> fresh
      worker live + lost tags handed back), throughput dip vs a clean
      run. Asserted: output exactly-once and in order, re-emitted tags ==
      measured lost tags, dedup ledger untouched.
    * ``faults/ckpt_checksum`` — per-entry CRC32 on vs off: synchronous
      save of a fixed ~2 MB state, per-save wall time. The on/off ratio
      is the integrity tax on the serialize path.
    """
    from repro.core.relic_pool import RelicPool
    from repro.runtime.chaos import KillSwitch

    def noop():
        return None

    n = max(iters, 200)
    reps = 5

    em.header("faults: supervised pool cost + kill-a-lane detection/"
              f"recovery (n={n} tasks/burst, {reps} bursts, respawn on)")

    # -- the supervised pool's per-task cost ------------------------------
    pool = RelicPool(lanes=2, capacity=256).start()
    batch = [(noop, (), {})] * n
    pool.submit_batch(batch)               # warm the lanes
    pool.wait()
    t0 = time.perf_counter()
    for _ in range(reps):
        pool.submit_batch(batch)
        pool.wait()
    dt = time.perf_counter() - t0
    pool.shutdown()
    em.row("faults/overhead/lanes2", dt / (reps * n) * 1e6,
           f"lanes=2;n={n};reps={reps}")

    # -- kill-a-lane: detection, recovery, throughput dip -----------------
    def timed_run(lanes, kill):
        # start_awake: detection is measured from the polling loop, so the
        # lanes must be draining (a parked lane never pops the poisoned
        # burst and the kill would only fire inside wait()).
        pool = RelicPool(lanes=lanes, capacity=max(n // lanes * 2, 64),
                         respawn=True, start_awake=True).start()
        ks = KillSwitch(after_bursts=0).arm(pool._lanes[1]) if kill else None
        batch = [(noop, (), {})] * n
        t_start = time.perf_counter()
        pool.submit_batch(batch)
        detect_s = recover_s = 0.0
        failure = None
        if kill:
            deadline = time.perf_counter() + 10.0
            while not failure and time.perf_counter() < deadline:
                got = pool.check_lanes()
                if got:
                    failure = got[0]
                time.sleep(0)
            detect_s = time.perf_counter() - t_start
            while ((pool.in_flight_estimate() > 0
                    or len(pool.live_lanes) < lanes)
                   and time.perf_counter() < deadline):
                time.sleep(0)
            recover_s = time.perf_counter() - t_start - detect_s
            pool.take_lane_failures()      # consumed: wait() is clean below
        pool.wait()
        total_s = time.perf_counter() - t_start
        if kill:
            assert failure is not None, "kill armed but never detected"
            assert ks.fired, "kill switch never fired"
            # THE acceptance invariant: lost == the dead ring's in-flight
            # count at death, and the global ledger balances around it.
            assert failure.lost == failure.submitted - failure.completed
            assert failure.lost > 0 and failure.respawned
            assert (pool.stats.completed + pool.lost_tasks
                    == pool.stats.submitted)
            assert pool.live_lanes == tuple(range(lanes))
        pool.shutdown()
        return total_s, detect_s, recover_s, failure

    for lanes in (2, 4):
        clean_s, _, _, _ = timed_run(lanes, kill=False)
        faulted_s, detect_s, recover_s, failure = timed_run(lanes, kill=True)
        dip = faulted_s / max(clean_s, 1e-9)
        em.row(f"faults/kill/lanes{lanes}/detect", detect_s * 1e6,
               f"lost={failure.lost};submitted={failure.submitted}")
        em.row(f"faults/kill/lanes{lanes}/recover", recover_s * 1e6,
               "respawned=ok;survivors_drained=ok")
        em.row(f"faults/kill/lanes{lanes}/run", faulted_s / n * 1e6,
               f"clean={clean_s / n * 1e6:.2f}us;dip=x{dip:.2f};"
               f"lost={failure.lost};ledger=ok")

    # -- stream stratum: kill a farm worker mid-stream (PR 10) ------------
    from repro.runtime.chaos import StageKillSwitch
    from repro.stream import Farm, Pipeline

    def ident(x):
        return x

    def farm_run(workers, kill):
        farm = Farm(ident, workers=workers, respawn=True, capacity=16)
        ks = (StageKillSwitch(after_items=5).arm(farm._workers[1])
              if kill else None)
        t0 = time.perf_counter()
        with Pipeline([farm]) as pipe:
            out = pipe.run(range(n))
        total_s = time.perf_counter() - t0
        # exactly-once, in order — with or without the kill
        assert out == list(range(n)), "farm dropped/duplicated items"
        detect_s = recover_s = 0.0
        failure = None
        if kill:
            fails = farm.take_worker_failures()
            assert ks.fired, "stage kill switch never fired"
            assert len(fails) == 1, f"expected 1 worker death, {len(fails)}"
            failure = fails[0]
            assert failure.respawned and failure.reemitted
            # THE acceptance invariant at this stratum: replayed tags ==
            # the dealt-minus-released loss, exactly once.
            assert sorted(farm.reemitted_tags) == list(failure.lost_tags)
            assert farm.dup_dropped == 0
            detect_s = failure.detected_s - ks.fired_t
            recover_s = failure.recovered_s - failure.detected_s
        return total_s, detect_s, recover_s, failure

    for workers in (2, 4):
        clean_s, _, _, _ = farm_run(workers, kill=False)
        faulted_s, detect_s, recover_s, failure = farm_run(workers, kill=True)
        dip = faulted_s / max(clean_s, 1e-9)
        em.row(f"faults/stage_kill/workers{workers}/detect", detect_s * 1e6,
               f"lost={len(failure.lost_tags)};"
               f"killed_after={failure.lost_tags[0] if failure.lost_tags else 'none'}")
        em.row(f"faults/stage_kill/workers{workers}/recover",
               recover_s * 1e6, "respawned=ok;reemitted==lost")
        em.row(f"faults/stage_kill/workers{workers}/run",
               faulted_s / n * 1e6,
               f"clean={clean_s / n * 1e6:.2f}us;dip=x{dip:.2f};"
               f"lost={len(failure.lost_tags)};dups=0;ledger=ok")

    # -- persistence stratum: checksum save overhead ----------------------
    import tempfile

    import numpy as np

    from repro.checkpoint import CheckpointManager

    rng = np.random.default_rng(42)
    state = {f"layer{i}/w": rng.standard_normal((256, 256)).astype(np.float32)
             for i in range(8)}                       # ~2 MB of entries
    ck_reps = 5
    ck_us = {}
    for checksum in (True, False):
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td, keep=2, async_=False,
                                    checksum=checksum)
            mgr.save(state, 0)                        # warm the dir
            t0 = time.perf_counter()
            for r in range(ck_reps):
                mgr.save(state, r + 1)
            dt = time.perf_counter() - t0
        tag = "on" if checksum else "off"
        ck_us[tag] = dt / ck_reps * 1e6
        em.row(f"faults/ckpt_checksum/{tag}", ck_us[tag],
               f"entries=8;mb=2;reps={ck_reps}")
    em.comment(f"ckpt checksum overhead: x"
               f"{ck_us['on'] / max(ck_us['off'], 1e-9):.3f} "
               "(on/off; CRC32 over stored bytes)")


def run_roofline(iters: int, em: Emitter):
    del iters  # summary of recorded artifacts; nothing to measure
    from benchmarks.roofline import load_records

    recs = load_records()
    if not recs:
        em.comment("roofline: no dry-run artifacts (run repro.launch.dryrun)")
        return
    em.header("roofline: dominant term per dry-run cell (seconds/step)")
    for r in recs:
        tag = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
        if "skipped" in r:
            em.row(tag, 0.0, "skipped")
            continue
        t = r["roofline_terms_s"]
        dom = r["dominant"]
        em.row(tag, t[dom] * 1e6,
               f"dominant={dom};ratio={r.get('useful_flops_ratio') or 0:.3f}")


# The section registry: name -> runner, every runner ``fn(iters, em)``.
# This dict is THE source of truth for --only/--list-sections, and
# tests/test_serve.py tripwires it against the module's run_* functions so
# a new section cannot be added without being reachable from the CLI.
SECTION_RUNNERS = {
    "fig1": run_figures,
    "spsc": run_spsc,
    "wavefront": run_wavefront,
    "grain": run_grain,
    "paper": run_paper,
    "scaling": run_scaling,
    "skew": run_skew,
    "serve": run_serve,
    "faults": run_faults,
    "roofline": run_roofline,
    "stream": run_stream,
}
SECTIONS = list(SECTION_RUNNERS)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--only", default="all",
                    help="section, or comma-separated list of sections, to "
                         f"run (default all): {','.join(SECTIONS)}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write per-section results (µs + speedups) to "
                         "this JSON file, e.g. BENCH_new.json")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="compare this run's rows against an earlier BENCH "
                         "file; any row slower by more than --compare-tol "
                         "is flagged and the process exits non-zero (the "
                         "measured-trajectory gate)")
    ap.add_argument("--compare-tol", type=float, default=0.25,
                    help="relative slowdown tolerance for --compare "
                         "(default 0.25 = +25%%)")
    ap.add_argument("--compare-metric", default="us",
                    choices=["us", "speedup"],
                    help="what --compare gates on: absolute µs per row "
                         "(same-phase baselines) or the row's recorded "
                         "speedup-over-serial (host drift cancels; the "
                         "cross-session trajectory gate)")
    ap.add_argument("--meta", action="append", default=[], metavar="KEY=VAL",
                    help="extra annotation recorded under meta.notes in the "
                         "--json payload (repeatable), e.g. baselines from "
                         "an earlier PR measured on the same host")
    ap.add_argument("--list-sections", action="store_true",
                    help="print the known section names and exit")
    args = ap.parse_args(argv)
    if args.list_sections:
        for name in SECTIONS:
            print(name)
        raise SystemExit(0)
    selected = (set(SECTIONS) if args.only == "all"
                else {s.strip() for s in args.only.split(",") if s.strip()})
    unknown = selected - set(SECTIONS)
    if unknown or not selected:
        raise SystemExit(
            f"--only: unknown section(s) {sorted(unknown)}; "
            f"choose from {SECTIONS} (comma-separated) or 'all'")
    # Fail fast on a bad --compare path: validate the baseline before any
    # benchmark section spends time measuring.
    baseline = load_baseline(args.compare) if args.compare else None
    em = Emitter()
    t0 = time.time()
    for name, runner in SECTION_RUNNERS.items():
        if name in selected:
            runner(args.iters, em)
    total = time.time() - t0
    print(f"# total {total:.1f}s")
    compared = regressions = None
    if baseline is not None:
        compared, regressions = compare_against(
            em, baseline, args.compare_tol, label=args.compare,
            metric=args.compare_metric)
    if args.json:
        import os

        from repro.runtime.config import (
            resolve_serve_config, resolve_spin_pause_every,
            resolve_supervise_config)

        # Host fingerprint: spin cadence + cpu_count + Python version
        # determine the spin/yield regime, so BENCH files are only
        # comparable across runs when these match. The cadence is the
        # per-instance resolution (RELIC_SPIN_PAUSE_EVERY aware), i.e.
        # what the substrates in this run actually used; ``serve`` is the
        # same per-instance resolution of the RELIC_SERVE_* knobs.
        meta = {
            "iters": args.iters, "only": args.only,
            "total_s": round(total, 1),
            "unix_time": int(time.time()),
            "python": sys.version.split()[0],
            "cpu_count": os.cpu_count(),
            "spin_pause_every": resolve_spin_pause_every(),
            "serve": resolve_serve_config().asdict(),
            "supervise": resolve_supervise_config().asdict(),
        }
        for kv in args.meta:
            key, _, val = kv.partition("=")
            meta.setdefault("notes", {})[key] = val
        if regressions is not None:
            meta["compare"] = {
                "baseline": args.compare, "tol": args.compare_tol,
                "metric": args.compare_metric,
                "compared_rows": compared, "regressions": regressions,
            }
        em.dump(args.json, meta=meta)
    if regressions or compared == 0 and baseline is not None:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.config import enable_compile_cache

    enable_compile_cache()
    main()

"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --control-seeds 3

For each seed, in one process and at the cell's own size, it prints one
JSON line with the numbers that the cell's check compares, each set of
them judged as a run would judge it (``correct``: ``Outcome.correct``
against the cell's limits):

- ``program``: the program's timed path against the plain reference (a
  short window: as many requests or steps as a run checks);
- ``control`` (first ``--control-seeds`` seeds): the reference put in the
  program's place, one precision below what the configuration states:
  int8 and fp8 products for a bf16 model that is served, bfloat16 weights
  and optimizer moments for float32 training, float32 products at
  ``Precision.HIGH`` and ``DEFAULT`` for the GAP kernels' float32 matrix
  products;
- ``faults`` (training, same seeds): the program with half of each batch
  left out; a state left unchanged reads 1 on ``update_gap`` by
  construction and needs no run.

The lower reading of a limit is the largest ``program`` value over the
seeds, the upper one the smallest ``control`` or fault value. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def judged(numbers: dict, limits: dict) -> dict:
    """``numbers`` with the ``correct`` that a run reading them would
    print."""
    from bench.drivers.common import Outcome

    checks = [(k, v, limits[k]) for k, v in numbers.items() if k in limits]
    return dict(numbers, correct=Outcome(
        attempted=1, failed=0, end_to_end={}, facts={}, checks=checks,
        device={}).correct)


def serve_readings(cell: dict, seeds: list, n_control: int):
    import jax.numpy as jnp
    import numpy as np

    from bench import weights
    from bench.drivers.common import dense_model_config
    from bench.drivers.serve_queue import served_gap
    from repro.launch import serve as serve_launch
    from repro.models import build_model

    c, t = cell["config_file"], cell["traffic_file"]
    limits = c["checks"]
    model = build_model(dense_model_config(
        c, param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"]))
    for i, seed in enumerate(seeds):
        params = weights.make_params(c, seed, c["param_dtype"])
        rng = np.random.default_rng([seed, 1])
        prompts = [rng.integers(0, c["vocab_size"], (t["batch"], t["prompt"]),
                                dtype=np.int32)
                   for _ in range(t["check_requests"])]
        resps = serve_launch.serve(model, params,
                                   [jnp.asarray(p) for p in prompts],
                                   gen=t["gen"], cache_len=t["cache_len"],
                                   lanes=t["lanes"])
        served = [np.concatenate([np.asarray(tok) for tok, _ in r.result()], 1)
                  for r in resps]
        del resps
        prog, ctrl = 0.0, {"int8": 0.0, "fp8": 0.0}
        for p, s in zip(prompts, served):
            gap, ref = served_gap(c, params, p, s)
            prog = max(prog, gap)
            if i < n_control:
                for mode in ctrl:
                    ctrl[mode] = max(ctrl[mode], served_gap(
                        c, params, p, s, mode, reference=ref)[0])
            del ref
        line = {"seed": seed,
                "program": judged({"served_logit_gap": prog}, limits)}
        if i < n_control:
            line["control"] = {m: judged({"served_logit_gap": v}, limits)
                               for m, v in ctrl.items()}
        del params
        yield line


def train_readings(cell: dict, seeds: list, n_control: int):
    import jax.numpy as jnp

    from bench.drivers import train_steps as ts
    from repro import sharding as shd

    c, t = cell["config_file"], cell["traffic_file"]
    n = t["checked_steps"]
    for i, seed in enumerate(seeds):
        model, oc, source, pipe, mesh, step_fn = ts.build(c, seed,
                                                          t["prefetch"])
        def step(state):
            b = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
            return step_fn(state, b)

        fed = iter(range(n))

        def half_step(state):   # the fault: half of each batch left out
            b = source.batch(next(fed))
            return step_fn(state, {k: jnp.asarray(v[:c["batch"] // 2])
                                   for k, v in b.items()})

        try:
            with shd.use_sharding_rules(mesh):
                state = ts.first_state(c, seed, model, mesh)
                state, *prog = ts.checked_steps(c, seed, oc, step, state, n)
                del state
                if i < n_control:
                    state = ts.first_state(c, seed, model, mesh)
                    state, *faulty = ts.checked_steps(c, seed, oc, half_step,
                                                      state, n)
                    del state
        finally:
            pipe.stop()
        del step_fn
        ref = ts.reference_steps(c, seed, source, n, t["reference_rows"])
        limits = c["checks"]
        line = {"seed": seed,
                "program": judged(ts.compare(*prog, ref), limits)}
        if i < n_control:
            low = ts.reference_steps(c, seed, source, n, t["reference_rows"],
                                     param_dtype="bfloat16")
            line["control"] = {"bf16_master": judged(ts.compare(*low, ref),
                                                     limits)}
            line["faults"] = {
                "half_batch": judged(ts.compare(*faulty, ref), limits),
                "state_unchanged": judged({"update_gap": 1.0}, limits)}
        yield line


def gap_readings(cell: dict, seeds: list, n_control: int):
    import jax.numpy as jnp

    from bench.drivers.task_graph import compare, graph_input, worst_of
    from bench.reference import gap as ref_gap
    from repro.tasks.api import TaskScope
    from repro.tasks.graph import gap_task_graph

    c, t = cell["config_file"], cell["traffic_file"]
    with TaskScope(c["substrate"]) as scope:
        for i, seed in enumerate(seeds):
            adj, w = graph_input(c, seed)
            graph = gap_task_graph(jnp.asarray(adj), jnp.asarray(w),
                                   c["source_node"])
            ref = ref_gap.gap_suite(adj, w, c)
            worst = worst_of((graph.run(scope)
                              for _ in range(t["check_graphs"])), ref)
            line = {"seed": seed, "program": judged(worst, c["checks"])}
            if i < n_control:
                line["control"] = {
                    name: judged(compare(ref_gap.gap_suite(
                        adj, w, c, functools.partial(
                            ref_gap.lowp_matvec, passes=passes)), ref),
                        c["checks"])
                    for name, passes in (("high", 3), ("default", 1))}
            yield line


READINGS = {"serve_queue": serve_readings, "train_steps": train_readings,
            "task_graph": gap_readings}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the control")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.run import (check_devices, enable_cache, load_cell, load_spec,
                           set_runtime_env)

    set_runtime_env()
    enable_cache()
    cell = load_cell(load_spec(), args.workload)
    check_devices(cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in READINGS[cell["traffic_file"]["kind"]](
            cell, seeds, args.control_seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

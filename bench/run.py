"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. Everything
that belongs to it is found by name:

- ``bench/configs/<config>.json``: the configuration, as it is run;
- ``bench/traffic/<traffic>.json``: the traffic mix; its ``kind`` names
  the driver, ``bench/drivers/<kind>.py``, that sets the program up,
  measures the window and checks what the window produced;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.

One run is one process on the chips the cell asks for. It makes weights
and inputs from ``--seed``, warms up every shape it will use (set-up),
measures for ``--seconds``, then checks the window's outputs against the
plain reference. With ``--trace 0`` the result holds the cell's end-to-end
metrics; with ``--trace 1`` the window is profiled and the result holds the
per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, ``setup``: each set-up phase's seconds and JAX's compile-cache
hits and misses up to the window, and ``checks`` last: each number
compared beside its limit); the last lines of standard error repeat the
set-up and then the checks. A run that finds no TPU,
or fewer chips than the cell asks for, exits 1 and prints no result.
"""

from __future__ import annotations

T_START = __import__("time").perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"

# When the TPU runtime starts it maps a host buffer for transfers; at its
# default size, on a host without transparent huge pages, that takes 5 to
# 15 s and varies from run to run (half of a cell's set-up). The cells'
# transfers are small, and with 256 MiB the start takes about 2 s.
RUNTIME_ENV = {"TPU_PREMAPPED_BUFFER_SIZE": str(256 << 20)}


def set_runtime_env() -> None:
    """``RUNTIME_ENV``, where the environment does not set it; call before
    JAX starts its backend."""
    import os

    for name, value in RUNTIME_ENV.items():
        os.environ.setdefault(name, value)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(spec: dict, workload: str, root: Path = ROOT) -> dict:
    """The cell with its configuration and traffic files read from the
    checkout at ``root``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = dict(cells[workload])
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config_file"] = json.loads((root / cfg_entry["file"]).read_text())
    cell["traffic_file"] = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell


def driver_for(cell: dict):
    return importlib.import_module(
        f"bench.drivers.{cell['traffic_file']['kind']}")


def metric_reader(name: str):
    """``read(facts, trace) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(spec: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices


def enable_cache() -> None:
    """JAX's persistent compilation cache in the checkout's ``.jax_cache``
    (or ``JAX_COMPILATION_CACHE_DIR``), keeping every program, so that only
    a cell's first run in a checkout compiles."""
    import jax

    from repro.runtime.config import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def result_line(spec: dict, cell: dict, outcome, trace: bool,
                trace_numbers) -> dict:
    workload = cell["name"]
    metrics = {}
    if trace:
        for m in metrics_of(spec, workload, "per_layer"):
            value = metric_reader(m["name"])(outcome.facts, trace_numbers)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in metrics_of(spec, workload, "end_to_end"):
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = dict(outcome.device)
    line = {"correct": outcome.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_numbers["busy_s"]
        device["window_s"] = trace_numbers["window_s"]
        line["breakdown"] = trace_numbers["breakdown"]
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in outcome.checks}
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, root: Path = ROOT,
             t_start: float = None) -> dict:
    """Run one cell in this process; returns the result line. Tests pass
    ``require_chip=False`` to drive the rest of a run on the CPU, and a
    ``root`` holding their own small cells."""
    from bench import trace as tr
    from bench.drivers.common import Context, watch_compiles

    watch_compiles()
    spec = load_spec(root)
    cell = load_cell(spec, workload, root)
    ctx = Context(cell=cell, seed=seed, seconds=seconds,
                  t_start=T_START if t_start is None else t_start,
                  tracer=tr.Tracer(trace, str(
                      root / ".bench_out" / "trace" / workload)),
                  devices=[])
    ctx.mark("import")
    if require_chip:
        ctx.devices = check_devices(cell["chips"])
    else:
        import jax

        ctx.devices = jax.devices()
    ctx.mark("devices")
    outcome = driver_for(cell).run(ctx)
    trace_numbers = (tr.reduce_file(outcome.trace_path)
                     if trace and outcome.trace_path else None)
    if trace and trace_numbers is None:
        raise RuntimeError("traced run recorded no trace")
    line = result_line(spec, cell, outcome, trace, trace_numbers)
    line["setup"] = ctx.setup_report()
    line["checks"] = line.pop("checks")     # the checks come last
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    set_runtime_env()
    enable_cache()
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 1
    setup = line["setup"]
    print("setup " + " ".join(f"{k} {v:.3f}" for k, v in
                              setup["phases_s"].items()), file=sys.stderr)
    print("setup " + " ".join(f"{k} {v:.6g}" for k, v in setup.items()
                              if k != "phases_s"), file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

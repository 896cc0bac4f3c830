"""Readings that the limits of ``correct`` of a latent-attention MoE serving
cell are set from (traffic kind ``serve_queue_mla_moe``).

    python3 bench/control_mla_moe.py --workload <cell> --seeds 1,2,3 --control-seeds 3

As ``bench/control.py`` does for the other kinds: for each seed, in one
process and at the cell's own size, one JSON line with the numbers the
cell's check compares, each set judged as a run would judge it:

- ``program``: ``serve()`` answers ``check_requests`` requests (the first
  prompts the seed gives), and the check reads ``check_rows`` rows of each;
- ``control`` (first ``--control-seeds`` seeds): the reference in int8 and
  in fp8 products put in the program's place, one precision below the
  configuration's bf16, over the same rows and served prefix;
- ``faults`` (same seeds): every served token one id on (``shifted``).

The lower reading of a limit is the largest ``program`` value over the
seeds, the upper one the smallest ``control`` or fault value. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seed(c: dict, t: dict, model, seed: int, modes) -> dict:
    """The worst of each number over the checked rows of one seed's
    requests, for the program and each of ``modes``."""
    import jax.numpy as jnp
    import numpy as np

    from bench import weights_mla_moe as wm
    from bench.drivers import serve_queue_mla_moe as drv
    from repro.launch import serve as serve_launch

    params = wm.make_params(c, seed, c["param_dtype"])
    rng = np.random.default_rng([seed, 1])
    prompts = [rng.integers(0, c["vocab_size"], (t["batch"], t["prompt"]),
                            dtype=np.int32)
               for _ in range(t["check_requests"])]
    resps = serve_launch.serve(model, params, [jnp.asarray(p) for p in prompts],
                               gen=t["gen"], cache_len=t["cache_len"],
                               lanes=t["lanes"])
    served = [[np.concatenate([np.asarray(v[i]) for v in r.result()], 1)
               for i in (0, 1)] for r in resps]
    del resps
    rows_rng = np.random.default_rng([seed, 3])
    worst: dict = {}
    for p, (toks, logits) in zip(prompts, served):
        rows = drv.sample_rows(rows_rng, t["batch"], t["check_rows"])
        for who, nums in drv.readings(c, params, p[rows], toks[rows],
                                      logits[rows], modes).items():
            old = worst.setdefault(who, nums)
            worst[who] = {k: max(v, old[k]) for k, v in nums.items()}
    return worst


def readings(cell: dict, seeds: list, n_control: int):
    from bench.control import judged
    from bench.drivers import serve_queue_mla_moe as drv

    c, t = cell["config_file"], cell["traffic_file"]
    limits = c["checks"]
    model = drv.build(c)
    for i, seed in enumerate(seeds):
        modes = ("int8", "fp8", "shifted") if i < n_control else ()
        worst = _seed(c, t, model, seed, modes)
        line = {"seed": seed, "program": judged(worst.pop("f32"), limits)}
        if "shifted" in worst:
            line["faults"] = {"shifted": judged(worst.pop("shifted"), limits)}
        if worst:
            line["control"] = {m: judged(v, limits) for m, v in worst.items()}
        yield line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also read the controls")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.run import (check_devices, enable_cache, load_cell, load_spec,
                           set_runtime_env)

    set_runtime_env()
    enable_cache()
    cell = load_cell(load_spec(), args.workload)
    check_devices(cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in readings(cell, seeds, args.control_seeds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

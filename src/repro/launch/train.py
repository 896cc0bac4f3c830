"""Training driver: data pipeline (Relic-prefetched) -> jit train step ->
async checkpointing -> straggler monitoring. Runs a real loop on whatever
devices exist (CPU here; the same code path jit-compiles for a pod).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch relic_tiny --steps 200 \
      --batch 8 --seq 256 --ckpt /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, PrefetchPipeline, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_state, make_train_step
from repro.models import build_model
from repro.optim import OptConfig
from repro.runtime import StragglerMonitor


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="relic_tiny")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-checksum", default="on", choices=["on", "off"],
                    help="per-entry CRC32 in the checkpoint manifest "
                         "(verified on restore)")
    ap.add_argument("--ckpt-chaos", default="",
                    help="chaos: crash the Nth save at a named fs point, "
                         "as point[:at_save] (e.g. 'manifest:1'); points: "
                         "serialize-start, entry, manifest, pre-publish")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    mesh = make_host_mesh()
    oc = OptConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                   total_steps=args.steps)

    dc = DataConfig(seq_len=args.seq, global_batch=args.batch,
                    vocab_size=cfg.vocab_size)
    pipe = PrefetchPipeline(SyntheticLM(dc), dc).start()

    with shd.use_sharding_rules(mesh):
        state = make_train_state(model, jax.random.PRNGKey(0))
        state_sh = shd.named_shardings(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         state), mesh)
        state = jax.tree.map(jax.device_put, state, state_sh)
        step_fn = jax.jit(make_train_step(model, oc), donate_argnums=(0,))

        mgr = None
        if args.ckpt:
            mgr = CheckpointManager(
                args.ckpt,
                # Chaos runs save synchronously so the injected FsCrash
                # unwinds the driver at the exact write point — the
                # closest single-process stand-in for dying mid-save.
                async_=not args.ckpt_chaos,
                checksum=args.ckpt_checksum == "on")
            if args.ckpt_chaos:
                from repro.runtime.chaos import FsFaultInjector
                point, _, at_save = args.ckpt_chaos.partition(":")
                FsFaultInjector(crash_point=point,
                                at_save=int(at_save or 0)).arm(mgr)
        start = 0
        if mgr and args.resume and mgr.latest_step() is not None:
            state, start = mgr.restore(state, shardings=state_sh)
            print(f"resumed from step {start}")

        mon = StragglerMonitor(n_hosts=1)
        t_last = time.time()
        metrics = {"loss": float("nan")}  # resumed at --steps: no step left
        try:
            for i in range(start, args.steps):
                batch = {k: jnp.asarray(v)
                         for k, v in pipe.next_batch().items()}
                state, metrics = step_fn(state, batch)
                if (i + 1) % args.log_every == 0 or i == start:
                    loss = float(metrics["loss"])
                    dt_step = (time.time() - t_last) / args.log_every
                    mon.record(0, dt_step)
                    t_last = time.time()
                    print(f"step {i+1:5d}  loss {loss:.4f}  "
                          f"lr {float(metrics['lr']):.2e}  "
                          f"gnorm {float(metrics['grad_norm']):.3f}  "
                          f"{dt_step*1e3:.0f} ms/step", flush=True)
                if mgr and (i + 1) % args.ckpt_every == 0:
                    mgr.save(state, i + 1)  # async on the Relic assistant
            if mgr:
                mgr.save(state, args.steps, block=True)
                mgr.close()
        finally:
            # A chaos FsCrash (or any error) must not leak the prefetch
            # threads into the caller's process — the resume test runs
            # main() twice in-process.
            pipe.stop()
        return float(metrics["loss"])


if __name__ == "__main__":
    from repro.runtime.config import enable_compile_cache

    enable_compile_cache()
    main()

"""Fault-tolerance walkthrough: train on a healthy mesh, checkpoint
asynchronously, "lose" half the data-parallel capacity, and resume on the
shrunken mesh from the same checkpoint — the elastic-restart path a 1000-node
deployment takes after a pod failure.

Spawns itself under XLA_FLAGS=--xla_force_host_platform_device_count=8 so the
mesh shrink (4x2 -> 2x2) is real.

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""

import os
import subprocess
import sys
from pathlib import Path

BODY = r"""
import os, tempfile
import jax, jax.numpy as jnp
import numpy as np
from repro import sharding as shd
from repro.checkpoint import CheckpointManager, elastic_restore
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_state, make_train_step
from repro.models import build_model
from repro.optim import OptConfig
from repro.runtime import HeartbeatTracker, plan_elastic_remesh

cfg = get_config("relic_tiny", smoke=True)
model = build_model(cfg)
oc = OptConfig(warmup_steps=2, total_steps=40)
dc = DataConfig(seq_len=64, global_batch=8, vocab_size=cfg.vocab_size)
src = SyntheticLM(dc)
step_fn = jax.jit(make_train_step(model, oc))

mesh_a = make_mesh((4, 2), ("data", "model"))
print(f"[healthy] mesh {dict(mesh_a.shape)}")
with shd.use_sharding_rules(mesh_a):
    state = make_train_state(model, jax.random.PRNGKey(0))
    shs = shd.named_shardings(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state), mesh_a)
    state = jax.tree.map(jax.device_put, state, shs)
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in src.batch(i).items()}
        state, m = step_fn(state, batch)
    print(f"[healthy] step 6 loss {float(m['loss']):.4f}")
    ckpt = tempfile.mkdtemp()
    mgr = CheckpointManager(ckpt, async_=True)
    mgr.save(state, 6)          # async on the Relic assistant
    mgr.wait()

# --- failure: two hosts (half the data axis) stop heartbeating -------------
t = {"now": 0.0}
hb = HeartbeatTracker(n_hosts=4, timeout_s=30, clock=lambda: t["now"])
t["now"] = 60.0
for h in (0, 1):
    hb.beat(h)
dead = hb.dead()
print(f"[failure] dead hosts: {dead}")
plan = plan_elastic_remesh((4, 2), ("data", "model"), dead, chips_per_host=1,
                           restore_step=6)
print(f"[plan] {plan.old_shape} -> {plan.new_shape}, resume @ {plan.restore_step}")

# --- elastic restart on the surviving mesh ---------------------------------
mesh_b = make_mesh(plan.new_shape, plan.axes)
with shd.use_sharding_rules(mesh_b):
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored, at_step = elastic_restore(mgr, state, mesh_b,
                                        step=plan.restore_step)
    print(f"[restart] restored step {at_step} onto {dict(mesh_b.shape)}")
    for i in range(6, 10):
        batch = {k: jnp.asarray(v) for k, v in src.batch(i).items()}
        restored, m = step_fn(restored, batch)
    print(f"[restart] step 10 loss {float(m['loss']):.4f} — training continued")
mgr.close()
print("elastic restart OK")
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # 8 virtual host devices, never a chip
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    r = subprocess.run([sys.executable, "-c", BODY], env=env)
    raise SystemExit(r.returncode)


if __name__ == "__main__":
    main()

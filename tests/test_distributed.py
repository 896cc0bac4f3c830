"""SPMD tests on 8 fake host devices.

jax pins the device count at first init, so each test execs a fresh python
with XLA_FLAGS=--xla_force_host_platform_device_count=8 and asserts inside
the subprocess (non-zero exit = failure)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_spmd(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
    """) + textwrap.dedent(body)
    # 8 virtual host devices, never a chip
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_collective_matmul_ring_matches_ref():
    run_spmd("""
        from repro.core.collective_matmul import (
            tp_allgather_matmul, tp_matmul_reducescatter)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(48, 32)), jnp.float32)
        y = tp_allgather_matmul(x, w1, mesh)
        assert float(jnp.abs(y - x @ w1).max()) < 1e-4
        z = tp_matmul_reducescatter(y, w2, mesh)
        assert float(jnp.abs(z - (x @ w1) @ w2).max()) < 1e-3
        # unoverlapped references agree too
        y2 = tp_allgather_matmul(x, w1, mesh, overlapped=False)
        z2 = tp_matmul_reducescatter(y, w2, mesh, overlapped=False)
        assert float(jnp.abs(y2 - y).max()) < 1e-4
        assert float(jnp.abs(z2 - z).max()) < 1e-3
    """)


def test_train_step_sharded_2d_matches_single_device():
    run_spmd("""
        from repro import sharding as shd
        from repro.configs import get_config
        from repro.models import build_model
        from repro.launch.steps import make_train_state, make_train_step
        from repro.launch.mesh import make_mesh
        from repro.optim import OptConfig

        cfg = get_config("granite_8b", smoke=True)
        model = build_model(cfg)
        oc = OptConfig(warmup_steps=1, total_steps=10)
        rng = np.random.default_rng(0)
        b, s = 4, 32
        batch = {
          "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
          "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
          "mask": jnp.ones((b, s), jnp.float32),
        }
        # single device
        state = make_train_state(model, jax.random.PRNGKey(0))
        _, m1 = jax.jit(make_train_step(model, oc))(state, batch)

        # 2D sharded
        mesh = make_mesh((4, 2), ("data", "model"))
        with shd.use_sharding_rules(mesh):
            state2 = make_train_state(model, jax.random.PRNGKey(0))
            shs = shd.named_shardings(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state2), mesh)
            state2 = jax.tree.map(jax.device_put, state2, shs)
            step = jax.jit(make_train_step(model, oc))
            _, m2 = step(state2, batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"])
        assert abs(l1 - l2) / abs(l1) < 5e-2, (l1, l2)
    """)


def test_elastic_reshard_roundtrip():
    run_spmd("""
        import tempfile
        from repro import sharding as shd
        from repro.checkpoint import CheckpointManager, elastic_restore
        from repro.configs import get_config
        from repro.models import build_model
        from repro.launch.steps import make_train_state
        from repro.launch.mesh import make_mesh

        cfg = get_config("granite_8b", smoke=True)
        model = build_model(cfg)
        mesh_a = make_mesh((4, 2), ("data", "model"))   # healthy fleet
        mesh_b = make_mesh((2, 2), ("data", "model"))   # after losing hosts

        with shd.use_sharding_rules(mesh_a):
            state = make_train_state(model, jax.random.PRNGKey(0))
            shs = shd.named_shardings(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state), mesh_a)
            state = jax.tree.map(jax.device_put, state, shs)

        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, async_=False)
            mgr.save(state, 7)
            restored, step = elastic_restore(mgr, state, mesh_b)
            assert step == 7
            for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            # restored arrays really live on mesh_b
            leaf = jax.tree.leaves(restored)[0]
            assert leaf.sharding.mesh.shape == mesh_b.shape
    """)


def test_compressed_psum_close_to_exact():
    run_spmd("""
        from repro.optim.compression import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("pod",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)

        def f(xs):
            return compressed_psum(xs, "pod")

        out = jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                            out_specs=P("pod", None))(x)
        want = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        err = float(jnp.abs(out - want).max())
        scale = float(jnp.abs(x).max()) / 127
        assert err <= 8 * scale + 1e-6, (err, scale)
    """)


def test_pipeline_parallel_matches_sequential():
    """GPipe over the pod axis: forward exact, gradients correct."""
    run_spmd("""
        from repro.core.pipeline import pipeline_apply, split_stages
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("pod", "model"))
        rng = np.random.default_rng(0)
        L, D = 8, 16
        ws = jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32)
        M, mb, S = 6, 2, 4
        x = jnp.asarray(rng.normal(size=(M, mb, S, D)), jnp.float32)

        def layer(w, h):
            return jnp.tanh(h @ w)

        def stage_fn(stage_ws, h):
            h, _ = jax.lax.scan(lambda h, w: (layer(w, h), None), h, stage_ws)
            return h

        def seq_apply(ws_, xm):
            out, _ = jax.lax.scan(lambda h, w: (layer(w, h), None), xm, ws_)
            return out

        ref = jax.vmap(lambda xm: seq_apply(ws, xm))(x)
        stages = split_stages(ws, 4)
        out = pipeline_apply(stage_fn, stages, x, mesh)
        assert float(jnp.abs(out - ref).max()) < 1e-6

        g_pipe = jax.grad(lambda w_, x_: jnp.sum(
            pipeline_apply(stage_fn, w_, x_, mesh) ** 2))(stages, x)
        g_seq = jax.grad(lambda w_, x_: jnp.sum(
            jax.vmap(lambda xm: seq_apply(w_, xm))(x_) ** 2))(ws, x)
        err = float(jnp.abs(g_pipe.reshape(L, D, D) - g_seq).max())
        assert err < 1e-4, err
    """)


def test_dryrun_single_cell_on_8_devices():
    """End-to-end dry-run machinery on a small mesh (fast sanity — the full
    512-device run is exercised by repro.launch.dryrun itself)."""
    run_spmd("""
        from repro import sharding as shd
        from repro.configs import get_config, SHAPES
        from repro.launch.mesh import make_mesh
        from repro.launch import dryrun as dr

        cfg = get_config("granite_8b", smoke=True).replace(scan_layers=True)
        mesh = make_mesh((4, 2), ("data", "model"))
        shape = SHAPES["train_4k"]
        import dataclasses
        shape = dataclasses.replace(shape, seq_len=128, global_batch=8)
        lowered, compiled, meta = dr.lower_cell(cfg, shape, mesh)
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes > 0
        ca = compiled.cost_analysis()
        assert ca.get("flops", 0) > 0
        colls = dr.parse_collectives(compiled.as_text())
        assert colls["total"] > 0
    """)

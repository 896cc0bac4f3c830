"""End-to-end system behaviour: train-to-convergence smoke, serve loop,
sharding rules coverage."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sharding as shd
from repro.configs import SHAPES, get_config, shape_applicable


def test_training_reduces_loss():
    """A few hundred steps of the tiny config must reduce loss materially."""
    from repro.launch.train import main as train_main

    final = train_main(["--arch", "relic_tiny", "--smoke", "--steps", "100",
                        "--batch", "8", "--seq", "64", "--log-every", "50"])
    assert final < 5.0, final  # ln(512) ≈ 6.24 at init


def test_serve_generates_tokens():
    from repro.launch.serve import main as serve_main

    gen = serve_main(["--arch", "relic_tiny", "--smoke", "--batch", "2",
                      "--prompt-len", "4", "--gen", "8"])
    assert gen.shape == (2, 8)
    assert (np.asarray(gen) >= 0).all()


def test_shape_applicability_rules():
    dense = get_config("llama3_405b")
    ssm = get_config("rwkv6_1p6b")
    hyb = get_config("zamba2_1p2b")
    ok, why = shape_applicable(dense, SHAPES["long_500k"])
    assert not ok and "sub-quadratic" in why
    assert shape_applicable(ssm, SHAPES["long_500k"])[0]
    assert shape_applicable(hyb, SHAPES["long_500k"])[0]
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        assert shape_applicable(dense, SHAPES[s])[0]


def test_param_rules_cover_every_arch():
    """Every parameter of every full config matches a sharding rule that
    fits its shape (after divisibility fallback)."""
    from repro.configs import all_configs
    from repro.launch.mesh import make_mesh

    # 16 devices not required: specs are mesh-shape-checked lazily; use
    # a tiny mesh with the production axis names via AbstractMesh-like shape
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch, cfg in all_configs().items():
        from repro.models import build_model

        sds = jax.eval_shape(lambda m=build_model(cfg): m.init(
            jax.random.PRNGKey(0)))
        specs = shd.param_specs(sds, mesh)
        big_unsharded = []
        for (kp, leaf), spec in zip(
                jax.tree_util.tree_flatten_with_path(sds)[0],
                jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))):
            assert isinstance(spec, jax.sharding.PartitionSpec)
        assert specs is not None


def test_input_specs_cover_all_cells():
    from repro.configs import all_configs
    from repro.models import build_model

    for arch, cfg in all_configs().items():
        model = build_model(cfg)
        for sname, shape in SHAPES.items():
            ok, _ = shape_applicable(cfg, shape)
            if not ok:
                continue
            batch, cache_len = model.input_specs(shape)
            assert "tokens" in batch
            if shape.kind == "decode":
                assert cache_len == shape.seq_len
                assert batch["tokens"].shape == (shape.global_batch, 1)
            else:
                assert batch["tokens"].shape[0] == shape.global_batch


def test_fit_spec_divisibility():
    # AbstractMesh: fit_spec only consults axis names/sizes, no devices needed
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # 20 heads do not divide model=4*? -> drops axis
    spec = shd.fit_spec(mesh, [None, "model", None], (3, 20, 64))
    assert spec == jax.sharding.PartitionSpec(None, "model", None)
    spec = shd.fit_spec(mesh, [None, "model", None], (3, 21, 64))
    assert spec == jax.sharding.PartitionSpec(None, None, None)
    spec = shd.fit_spec(mesh, [("data", "model"), None], (16, 8))
    assert spec == jax.sharding.PartitionSpec(("data", "model"), None)
    # batch=2 divides data(2) but not data*model(8): degrade to prefix
    spec = shd.fit_spec(mesh, [("data", "model"), None], (2, 8))
    assert spec == jax.sharding.PartitionSpec("data", None)


def _compile_cache_child(tmp_path, env_dir=None):
    """Run enable_compile_cache() in a fresh process (it changes
    process-wide JAX config); returns (helper's dir, JAX's dir). With
    ``env_dir`` set as JAX_COMPILATION_CACHE_DIR the child also compiles one
    program; without it, it compiles nothing, so no test writes into the
    checkout's cache."""
    root = Path(__file__).resolve().parent.parent
    lines = ["import jax, jax.numpy as jnp",
             "from repro.runtime.config import enable_compile_cache",
             "d = enable_compile_cache()"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
        lines += [
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)",
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()"]
    lines.append("print(d); print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", "\n".join(lines)], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_compile_cache_follows_env_var(tmp_path):
    cache = tmp_path / "cc"
    helper_dir, jax_dir = _compile_cache_child(tmp_path, cache)
    assert helper_dir == jax_dir == str(cache)
    assert any(cache.iterdir())  # the compile was written there


def test_compile_cache_default_is_one_fixed_ignored_path(tmp_path):
    from repro.runtime.config import COMPILE_CACHE_DIR

    root = Path(__file__).resolve().parent.parent
    first = _compile_cache_child(tmp_path)
    assert first == [str(root / ".jax_cache")] * 2
    assert _compile_cache_child(tmp_path) == first  # no pid/time in it
    assert COMPILE_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()

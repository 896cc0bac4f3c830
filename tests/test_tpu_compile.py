"""Compile-only checks of the main path for a described TPU v5e.

Nothing runs here: each test compiles for a ``v5e:2x2`` topology described
to the installed TPU compiler, which refuses what the chip would refuse —
Pallas block shapes that do not tile, kernels that need more VMEM than they
may use, and programs that do not fit HBM. Interpret mode
(``tests/test_kernels.py``) checks none of that.

The topology is described inside a fixture, never while a module is
imported, so every pytest worker collects the same tests and only the
worker that runs this file loads the TPU library. Keep every such compile
in this one file.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro import sharding as shd
from repro.configs import get_config
from repro.core.collective_matmul import (tp_allgather_matmul,
                                          tp_matmul_reducescatter)
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.relic_matmul import relic_matmul, relic_matmul_gated
from repro.kernels.ssd import ssd_bhtp
from repro.kernels.wkv6 import wkv6_bhtk
from repro.launch.steps import (make_prefill_step, make_serve_step,
                                make_train_state, make_train_step)
from repro.models import build_model
from repro.optim import OptConfig

GiB = 2 ** 30
# What one v5e chip's compiler lets a program use is 15.75 GiB of its 16.
HBM_BUDGET = 15 * GiB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topology = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(tree, sharding):
    return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tree)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _is_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 32, 32, 2048, 96),     # phi3-mini: head_dim 96, S 2048
    (8, 12, 4, 1024, 64),      # relic_tiny: GQA 12/4
], ids=["phi3_mini", "relic_tiny"])
def test_flash_attention_compiles(one_chip, b, h, hkv, s, d):
    q = _sds((b, h, s, d), jnp.bfloat16, one_chip)
    kv = _sds((b, hkv, s, d), jnp.bfloat16, one_chip)
    assert _is_kernel(_compile(flash_attention_bhsd, q, kv, kv))


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated"])
def test_relic_matmul_compiles(one_chip, gated):
    x = _sds((2048, 3072), jnp.bfloat16, one_chip)
    w = _sds((3072, 8192), jnp.bfloat16, one_chip)
    fn, args = ((relic_matmul_gated, (x, w, w)) if gated
                else (relic_matmul, (x, w)))
    assert _is_kernel(_compile(fn, *args))


def test_wkv6_compiles_at_rwkv6_widths(one_chip):
    cfg = get_config("rwkv6_1p6b")
    h, k, t = cfg.d_model // cfg.ssm.head_dim, cfg.ssm.head_dim, 2048
    act = _sds((1, h, t, k), jnp.bfloat16, one_chip)
    logw = _sds((1, h, t, k), jnp.float32, one_chip)
    u = _sds((h, k), jnp.float32, one_chip)
    fn = functools.partial(wkv6_bhtk, chunk=cfg.ssm.chunk)
    assert _is_kernel(_compile(fn, act, act, act, logw, u))


def test_ssd_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2_1p2b")
    s, t = cfg.ssm, 2048
    h = s.expand * cfg.d_model // s.head_dim
    x = _sds((1, h, t, s.head_dim), jnp.float32, one_chip)
    a = _sds((1, t, h), jnp.float32, one_chip)
    bc = _sds((1, t, s.state_dim), jnp.float32, one_chip)
    fn = functools.partial(ssd_bhtp, chunk=s.chunk)
    assert _is_kernel(_compile(fn, x, a, bc, bc))


@pytest.mark.parametrize("step", ["prefill", "serve_step"])
def test_phi3_serve_fits_one_chip(one_chip, step):
    """chip_smoke's serve phase at full phi3-mini width must fit one chip's
    HBM (the scan-of-decode prefill holds ~4x its cache in temporaries)."""
    cfg = get_config("phi3_mini_3p8b").replace(param_dtype="bfloat16")
    model = build_model(cfg)
    b, cache_len = chip_smoke.SERVE_BATCH, chip_smoke.SERVE_CACHE_LEN
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    cache = _placed(jax.eval_shape(lambda: model.init_cache(b, cache_len)),
                    one_chip)
    if step == "prefill":
        fn = make_prefill_step(model)
        args = (_sds((b, chip_smoke.PROMPT_LEN), jnp.int32, one_chip),)
    else:
        fn = make_serve_step(model)
        args = (_sds((b, 1), jnp.int32, one_chip),
                _sds((), jnp.int32, one_chip))
    ma = _compile(fn, params, cache, *args,
                  donate_argnums=(1,)).memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BUDGET, used / GiB


def test_relic_tiny_train_step_fits_one_chip(one_chip):
    model = build_model(get_config("relic_tiny"))
    state = _placed(jax.eval_shape(
        lambda: make_train_state(model, jax.random.PRNGKey(0))), one_chip)
    batch = {"tokens": _sds((8, 256), jnp.int32, one_chip),
             "labels": _sds((8, 256), jnp.int32, one_chip),
             "mask": _sds((8, 256), jnp.float32, one_chip)}
    ma = _compile(make_train_step(model, OptConfig()), state, batch,
                  donate_argnums=(0,)).memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BUDGET


def test_relic_tiny_train_step_compiles_on_2x2_mesh(topo):
    """chip_smoke --chips 4's sharded phase: the 2-D sharded step compiles
    for four chips and really partitions (collectives in the program)."""
    model = build_model(get_config("relic_tiny"))
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    rep = NamedSharding(mesh, P())
    with shd.use_sharding_rules(mesh):
        abstract = jax.eval_shape(
            lambda: make_train_state(model, jax.random.PRNGKey(0)))
        state = jax.tree.map(
            lambda x, sh: _sds(x.shape, x.dtype, sh), abstract,
            shd.named_shardings(abstract, mesh))
        batch = {"tokens": _sds((8, 256), jnp.int32, rep),
                 "labels": _sds((8, 256), jnp.int32, rep),
                 "mask": _sds((8, 256), jnp.float32, rep)}
        text = _compile(make_train_step(model, OptConfig()), state,
                        batch).as_text()
    assert "all-reduce" in text and "all-gather" in text


@pytest.mark.parametrize("overlapped", [True, False],
                         ids=["ring", "unoverlapped"])
def test_tp_collective_matmuls_compile_on_4_chips(topo, overlapped):
    """chip_smoke --chips 4's ring phase at phi3-mini MLP widths: the ring
    forms lower to collective-permutes, the references to all-gather /
    reduce-scatter."""
    mesh = Mesh(np.array(topo.devices), ("model",))
    s, d, f = 2048, 3072, 8192

    def sharded(shape, spec):
        return _sds(shape, jnp.bfloat16, NamedSharding(mesh, P(*spec)))

    ag = _compile(functools.partial(tp_allgather_matmul, mesh=mesh,
                                    overlapped=overlapped),
                  sharded((s, d), ("model", None)),
                  sharded((d, f), (None, "model"))).as_text()
    rs = _compile(functools.partial(tp_matmul_reducescatter, mesh=mesh,
                                    overlapped=overlapped),
                  sharded((s, f), (None, "model")),
                  sharded((f, d), ("model", None))).as_text()
    if overlapped:
        assert "collective-permute" in ag and "collective-permute" in rs
    else:
        assert "all-gather" in ag and "reduce-scatter" in rs

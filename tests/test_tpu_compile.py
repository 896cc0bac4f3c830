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
import math
import re

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
from repro.launch.serve import cache_programs, group_programs
from repro.launch.steps import make_train_state, make_train_step
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


def _phi3_serve_programs(one_chip, step, batch, cache_len, prompt_len):
    """phi3-mini's prefill or decode step at full width, compiled as
    ``serve()`` compiles them: the cache held in its default layout through
    the layer loops, and donated."""
    cfg = get_config("phi3_mini_3p8b").replace(param_dtype="bfloat16")
    model = build_model(cfg)
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    _, prefill, serve_step = cache_programs(model, params, batch, cache_len)
    cache = _placed(jax.eval_shape(lambda: model.init_cache(batch, cache_len)),
                    one_chip)
    if step == "serve_step":
        fn, args = serve_step, (_sds((batch, 1), jnp.int32, one_chip),
                                _sds((), jnp.int32, one_chip))
    else:
        fn, args = prefill, (_sds((batch, prompt_len), jnp.int32, one_chip),)
    return fn.lower(params, cache, *args).compile()


@pytest.mark.parametrize("step", ["prefill", "serve_step"])
def test_phi3_serve_fits_one_chip(one_chip, step):
    """chip_smoke's serve phase at full phi3-mini width must fit one chip's
    HBM."""
    ma = _phi3_serve_programs(
        one_chip, step, chip_smoke.SERVE_BATCH, chip_smoke.SERVE_CACHE_LEN,
        chip_smoke.PROMPT_LEN).memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < HBM_BUDGET, used / GiB


_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1}


def _copies_of_at_least(text, nbytes):
    """The ``copy`` instructions of a compiled program's HLO whose result
    holds at least ``nbytes``, as (name, shape)."""
    found = []
    for m in re.finditer(r"(%\S+) = (\w+)\[([\d,]*)\]\S* copy(?:-start)?\(",
                         text):
        size = _BYTES[m.group(2)] * math.prod(
            int(d) for d in m.group(3).split(",") if d)
        if size >= nbytes:
            found.append((m.group(1), f"{m.group(2)}[{m.group(3)}]"))
    return found


@pytest.mark.parametrize("step", ["prefill", "serve_step"])
def test_phi3_serve_keeps_the_cache_in_place(one_chip, step):
    """At the benchmark's serving sizes (batch 4, cache 512, prompt 64) the
    programs serve() builds copy no cache-sized array: the donated cache is
    the one buffer they read and write, in one layout. One layer's K slab
    is the smallest cache-sized array."""
    batch, cache_len, prompt_len = 4, 512, 64
    compiled = _phi3_serve_programs(one_chip, step, batch, cache_len,
                                    prompt_len)
    cfg = get_config("phi3_mini_3p8b")
    slab = batch * cache_len * cfg.n_kv_heads * cfg.resolved_head_dim * 2
    assert _copies_of_at_least(compiled.as_text(), slab) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("prompt_len", [64, 480])
def test_phi3_merged_group_fits_and_keeps_the_cache_in_place(one_chip,
                                                             prompt_len):
    """The phi3 serve cells' 4 queued [4, P] requests merge into one
    16-row group (under the v5e's ridge of 240 rows): its prefill and
    decode step fit one chip with the weights and the 16-row cache, and
    copy no cache-sized array."""
    cfg = get_config("phi3_mini_3p8b").replace(param_dtype="bfloat16")
    model = build_model(cfg)
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    _, prefill, step, join, split = group_programs(
        model, params, 4, jax.ShapeDtypeStruct((4, prompt_len), jnp.int32),
        None, 512)
    slab = 16 * 512 * cfg.n_kv_heads * cfg.resolved_head_dim * 2
    for compiled in (prefill, step):
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BUDGET
        assert _copies_of_at_least(compiled.as_text(), slab) == []
    assert join is not None and split is not None


def _dsv3_serve_programs(one_chip, step, batch, cache_len, prompt_len):
    """The benchmark cell ``dsv3-ep32-serve-decode``'s prefill or decode
    step (DeepSeek-V3 at published widths, 1 dense and 4 MoE layers, 8
    held experts), compiled as ``serve()`` compiles them."""
    from bench.run import load_cell, load_spec
    from bench.weights_mla_moe import model_config

    c = load_cell(load_spec(), "dsv3-ep32-serve-decode")["config_file"]
    model = build_model(model_config(c, param_dtype=c["param_dtype"],
                                     compute_dtype=c["compute_dtype"]))
    params = _placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                     one_chip)
    _, prefill, serve_step = cache_programs(model, params, batch, cache_len)
    cache = _placed(jax.eval_shape(lambda: model.init_cache(batch, cache_len)),
                    one_chip)
    if step == "serve_step":
        fn, args = serve_step, (_sds((batch, 1), jnp.int32, one_chip),
                                _sds((), jnp.int32, one_chip))
    else:
        fn, args = prefill, (_sds((batch, prompt_len), jnp.int32, one_chip),)
    return fn.lower(params, cache, *args).compile(), c


@pytest.mark.parametrize("step", ["prefill", "serve_step"])
def test_dsv3_serve_fits_and_keeps_the_latent_cache_in_place(one_chip, step):
    """At the cell's sizes (batch 256, cache 512, prompt 64) the programs
    serve() builds fit one chip's HBM and copy no part of the latent cache
    of a layer's size or more: no copy has the shape of one layer's
    ``c_kv`` or ``k_pe`` slab, or of their stacks, in any order of dims."""
    batch, cache_len, prompt_len = 256, 512, 64
    compiled, c = _dsv3_serve_programs(one_chip, step, batch, cache_len,
                                       prompt_len)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BUDGET, (
        ma.argument_size_in_bytes / GiB, ma.temp_size_in_bytes / GiB)
    slabs = {tuple(sorted(d for d in (n, batch, cache_len, w) if d > 1))
             for n in (1, c["num_hidden_layers"])
             for w in (c["kv_lora_rank"], c["qk_rope_head_dim"])}
    slab = batch * cache_len * c["qk_rope_head_dim"] * 2
    copied = [(name, shape) for name, shape in
              _copies_of_at_least(compiled.as_text(), slab)
              if tuple(sorted(int(d) for d in shape.split("[")[1][:-1]
                              .split(",") if int(d) > 1)) in slabs]
    assert copied == []


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

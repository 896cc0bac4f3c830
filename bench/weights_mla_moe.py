"""Seeded weights of a DeepSeek-V3-style model (latent attention, leading
dense layers, then routed and shared experts), made by the benchmark, and
the program's ``ModelConfig`` of such a configuration file.

The tree is the program's parameter layout (checked against its abstract
init by ``bench.weights.check_layout``): ``dense_layers`` (the leading
dense layers) and ``layers`` (the expert layers), each stacked. Scales as
``bench/weights.py``: embedding 0.02, projections ``fan_in ** -0.5``, norm
scales 1; the correction bias of the router's choice is drawn with
standard deviation ``SCORE_BIAS_STD`` (the configuration's ``assumed``),
small enough to change some choices.

Each leaf is made on the device by its own jitted call, from
``fold_in(seed_key(seed), index)``, in blocks along its first axis of at
most ``BLOCK`` values, so that no float32 copy larger than one block of
one leaf is ever live beside the weights already made.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.weights import _nest, seed_key

BLOCK = 1 << 24
SCORE_BIAS_STD = 0.01


def model_config(c: dict, **program):
    """The program's ``ModelConfig`` for the configuration file ``c``."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

    rs = c["rope_scaling"]
    return ModelConfig(
        name=c["name"], family="moe", n_layers=c["num_hidden_layers"],
        first_k_dense=c["first_k_dense_replace"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], rope_theta=c["rope_theta"],
        act=c["hidden_act"], tie_embeddings=c["tie_word_embeddings"],
        max_seq=c["max_position_embeddings"],
        mla=MLAConfig(q_lora_rank=c["q_lora_rank"],
                      kv_lora_rank=c["kv_lora_rank"],
                      qk_nope_head_dim=c["qk_nope_head_dim"],
                      qk_rope_head_dim=c["qk_rope_head_dim"],
                      v_head_dim=c["v_head_dim"]),
        rope_scaling=YaRNConfig(
            factor=rs["factor"],
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale_all_dim=rs["mscale_all_dim"]),
        moe=MoEConfig(
            n_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
            d_ff=c["moe_intermediate_size"],
            n_shared_experts=c["n_shared_experts"],
            scoring=c["scoring_func"], n_groups=c["n_group"],
            topk_group=c["topk_group"],
            score_bias=c["topk_method"] == "noaux_tc",
            routed_scaling_factor=c["routed_scaling_factor"],
            held_first=c["held_first"], n_held=c["n_routed_experts"]),
        source=c["source"], **program)


def _attn_specs(c: dict, n: int) -> dict:
    d, h = c["hidden_size"], c["num_attention_heads"]
    ql, kvl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return {
        ("wq_a",): ((n, d, ql), d ** -0.5),
        ("q_norm", "scale"): ((n, ql), None),
        ("wq_nope",): ((n, h, nope, ql), ql ** -0.5),
        ("wq_pe",): ((n, h, rope, ql), ql ** -0.5),
        ("wkv_a",): ((n, d, kvl + rope), d ** -0.5),
        ("kv_norm", "scale"): ((n, kvl), None),
        ("wk_b",): ((n, h, nope, kvl), kvl ** -0.5),
        ("wv_b",): ((n, h, kvl, v), kvl ** -0.5),
        ("wo",): ((n, h, v, d), (h * v) ** -0.5),
    }


def _mlp_specs(n: int, d: int, f: int) -> dict:
    return {("w_up",): ((n, d, f), d ** -0.5),
            ("w_down",): ((n, f, d), f ** -0.5),
            ("w_gate",): ((n, d, f), d ** -0.5)}


def leaf_specs(c: dict) -> dict:
    """``{path: (shape, scale)}`` of every leaf; scale ``None`` means ones."""
    d, v = c["hidden_size"], c["vocab_size"]
    k = c["first_k_dense_replace"]
    n = c["num_hidden_layers"] - k
    e, f = c["n_routed_experts"], c["moe_intermediate_size"]
    specs = {("embed", "table"): ((v, d), 0.02)}

    def stack(name, m, ffn):
        specs[(name, "ln1", "scale")] = ((m, d), None)
        specs.update({(name, "attn") + p: s
                      for p, s in _attn_specs(c, m).items()})
        specs[(name, "ln2", "scale")] = ((m, d), None)
        specs.update(ffn)

    if k:
        stack("dense_layers", k, {("dense_layers", "mlp") + p: s for p, s in
                                  _mlp_specs(k, d, c["intermediate_size"])
                                  .items()})
    moe = {
        ("router",): ((n, d, c["router_experts"]), d ** -0.5),
        ("w_gate",): ((n, e, d, f), d ** -0.5),
        ("w_up",): ((n, e, d, f), d ** -0.5),
        ("w_down",): ((n, e, f, d), f ** -0.5),
        ("score_bias",): ((n, c["router_experts"]), SCORE_BIAS_STD),
    }
    moe.update({("shared",) + p: s for p, s in _mlp_specs(
        n, d, c["n_shared_experts"] * f).items()})
    stack("layers", n, {("layers", "moe") + p: s for p, s in moe.items()})
    specs[("final_norm", "scale")] = ((d,), None)
    specs[("lm_head", "kernel")] = ((d, v), d ** -0.5)
    return specs


def _blocks(shape) -> int:
    """The fewest blocks along axis 0 that hold at most ``BLOCK`` values."""
    total = math.prod(shape)
    return next((b for b in range(1, shape[0] + 1)
                 if shape[0] % b == 0 and total // b <= BLOCK), shape[0])


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _leaf(key, index, shape, scale, dtype):
    if scale is None:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(key, index)
    n = _blocks(shape)

    def block(i):
        part = jax.random.normal(jax.random.fold_in(key, i),
                                 (shape[0] // n,) + shape[1:], jnp.float32)
        return (scale * part).astype(dtype)

    return jax.lax.map(block, jnp.arange(n)).reshape(shape)


def make_params(c: dict, seed: int, dtype) -> dict:
    """Every weight on the device, leaf by leaf, in ``dtype``."""
    key, dtype = seed_key(seed), jnp.dtype(dtype)
    flat = {}
    for i, (path, (shape, scale)) in enumerate(leaf_specs(c).items()):
        flat[path] = jax.block_until_ready(
            _leaf(key, i, shape, scale, dtype))
    return _nest(flat)

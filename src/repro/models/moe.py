"""Top-k routed mixture-of-experts that drops no token, over the experts
this chip holds.

  * **Routing over every expert.** The router scores all ``n_experts`` in
    float32 (softmax, or DeepSeek-V3's sigmoid with a correction bias that
    moves the choice but not the weights), optionally keeps the best
    ``topk_group`` of ``n_groups`` expert groups, and picks the top k.
  * **Held experts.** A layer holds the weights of experts
    ``[held_first, held_first + n_held)`` only (all of them by default), as
    one chip of an expert-parallel deployment does, and computes their part
    of each token's output; picks of other experts are another chip's work.
  * **No capacity, no drop.** The (token, pick) pairs are sorted by held
    expert and multiplied by one grouped product over the held experts
    (``jax.lax.ragged_dot``); picks of experts held elsewhere sort past the
    last group and contribute nothing. Every token's output depends on its
    own row alone. The product's row tile (``ragged_dot_tiling``, read by
    the TPU compiler) is about the rows an expert expects, so an expert
    with a few rows does not fill a 512-row tile in vain.
  * A shared expert (``n_shared_experts`` x ``d_ff`` wide) or a dense
    residual MLP (arctic) is added to every token.

Named scopes: ``moe.router``, ``moe.experts``, ``moe.shared``.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.xla_metadata import set_xla_metadata

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.layers import _act, _normal, dt, init_mlp, mlp
from repro.sharding import shard_act

HIGHEST = jax.lax.Precision.HIGHEST
EXPERTS = ("w_gate", "w_up", "w_down")     # the held experts' weights


def init_moe(cfg: ModelConfig, key):
    mc = cfg.moe
    assert mc is not None
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    pd = dt(cfg.param_dtype)
    d, f, e = cfg.d_model, mc.d_ff, mc.held[1]
    p = {
        "router": _normal(kr, (d, mc.n_experts), d ** -0.5, pd),
        "w_gate": _normal(kg, (e, d, f), d ** -0.5, pd),
        "w_up": _normal(ku, (e, d, f), d ** -0.5, pd),
        "w_down": _normal(kd, (e, f, d), f ** -0.5, pd),
    }
    if mc.score_bias:
        p["score_bias"] = jnp.zeros((mc.n_experts,), pd)
    if mc.n_shared_experts or mc.dense_residual:
        p["shared"] = init_mlp(cfg, ks, d, mc.n_shared_experts * f
                               if mc.n_shared_experts else cfg.d_ff)
    return p


def route(mc: MoEConfig, logits: jax.Array, bias=None):
    """logits: [N, E] float32 -> (expert_idx [N,K], weights [N,K], aux).

    ``bias`` (``[E]``) is added to the scores for the choice only; the
    weights are the chosen experts' own scores, renormalized to sum 1 and
    scaled by ``routed_scaling_factor``."""
    n, e = logits.shape
    if mc.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    if mc.n_groups > 1:
        grouped = choice.reshape(n, mc.n_groups, e // mc.n_groups)
        group_score = (grouped.max(-1) if bias is None
                       else jax.lax.top_k(grouped, 2)[0].sum(-1))
        _, keep = jax.lax.top_k(group_score, mc.topk_group)        # [N, kg]
        kept = jnp.zeros((n, mc.n_groups), bool).at[
            jnp.arange(n)[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(n, e)
    _, expert_idx = jax.lax.top_k(choice, mc.top_k)                 # [N, K]
    weights = jnp.take_along_axis(scores, expert_idx, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) \
        * mc.routed_scaling_factor

    # Load-balance aux loss (Switch-style) over all experts.
    probs = scores / scores.sum(-1, keepdims=True)
    picked = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32).sum(1)  # [N, E]
    aux = e * jnp.sum(probs.mean(0) * picked.mean(0) / mc.top_k)
    return expert_idx, weights, aux


def row_tile(rows: int, mc: MoEConfig) -> int:
    """Rows of the grouped product's tiles: the rows an expert expects
    (``rows * top_k / n_experts``) to a power of two, from the MXU's 128 up
    to the compiler's default 512."""
    expected = rows * mc.top_k / mc.n_experts
    return int(min(512, max(128, 2 ** math.ceil(math.log2(max(expected, 1))))))


def held_experts(cfg: ModelConfig, p, x: jax.Array, expert_idx: jax.Array,
                 weights: jax.Array, layer=None) -> jax.Array:
    """The held experts' part of each token's output: x [N, D], picks
    ``expert_idx``/``weights`` [N, K] -> [N, D] float32.

    ``p``'s expert weights are one layer's (``[e, ...]``) or, with
    ``layer``, every layer's (``[L, e, ...]``): this layer's picks then go
    to its own groups of the whole stack, which the grouped product reads
    in place (a slice of the stack would be a copy of the layer's
    weights)."""
    mc, cd = cfg.moe, dt(cfg.compute_dtype)
    n, k = expert_idx.shape
    first, count = mc.held
    ws = [p[name] for name in EXPERTS]
    offset = 0
    if ws[0].ndim == 4:
        offset = layer * count
        ws = [w.reshape(-1, *w.shape[2:]) for w in ws]
    groups = ws[0].shape[0]
    local = expert_idx.reshape(n * k) - first
    held = (local >= 0) & (local < count)
    group = jnp.where(held, local + offset, groups)  # other chips' picks last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=groups + 1)[:groups].astype(jnp.int32)
    rows = x.astype(cd)[order // k]                                 # [N*K, D]
    with set_xla_metadata(ragged_dot_tiling=f"{row_tile(n, mc)},512,512"):
        gate = jax.lax.ragged_dot(rows, ws[0].astype(cd), sizes)
        up = jax.lax.ragged_dot(rows, ws[1].astype(cd), sizes)
        h = (_act(cfg.act, gate) * up).astype(cd)
        out = jax.lax.ragged_dot(h, ws[2].astype(cd), sizes,
                                 preferred_element_type=jnp.float32)
    # Back to (token, pick) order; rows past the held groups are zero.
    back = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    out = out[back].reshape(n, k, -1)
    w = jnp.where(held, weights.reshape(n * k), 0.0).reshape(n, k, 1)
    return (out * w).sum(1)


def moe_ffn(cfg: ModelConfig, p, x: jax.Array,
            layer=None) -> Tuple[jax.Array, jax.Array]:
    """x: [B,S,D] -> (y [B,S,D], aux_loss). ``layer``: see
    ``held_experts``."""
    mc = cfg.moe
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope("moe.router"):
        logits = jnp.dot(flat.astype(jnp.float32),
                         p["router"].astype(jnp.float32), precision=HIGHEST)
        expert_idx, weights, aux = route(mc, logits, p.get("score_bias"))
    with jax.named_scope("moe.experts"):
        y = held_experts(cfg, p, flat, expert_idx, weights, layer)
        y = y.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp(cfg, p["shared"], x)
    y = shard_act(y, "batch", None, "model", kind="resid")
    return y, aux * mc.aux_loss_weight

"""DeepSeek-V3 (latent attention with YaRN, sigmoid routing with a
correction bias over groups, held experts, a shared expert) against the
benchmark's plain float32 reference (``bench/reference/deepseek_v3.py``)
on seeded weights at the SMOKE size, and its pieces against their closed
forms."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v3 as ref
from repro.configs import get_config
from repro.launch.steps import make_prefill_step
from repro.models import build_model, mla, moe
from repro.models.lm import lm_forward

# This chip's share of the SMOKE size's 16 routed experts: experts 4..11.
HELD_FIRST, N_HELD = 4, 8


def _cfg(compute_dtype="float32", held=True):
    cfg = get_config("deepseek_v3", smoke=True).replace(
        param_dtype="float32", compute_dtype=compute_dtype)
    if held:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, held_first=HELD_FIRST, n_held=N_HELD))
    return cfg


def _reference_config(cfg) -> dict:
    """The reference's configuration keys (the published names) of cfg."""
    m, y, e = cfg.mla, cfg.rope_scaling, cfg.moe
    first, held = e.held
    return {"rms_norm_eps": 1e-6, "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "kv_lora_rank": m.kv_lora_rank, "rope_theta": cfg.rope_theta,
            "n_group": e.n_groups, "topk_group": e.topk_group,
            "num_experts_per_tok": e.top_k,
            "routed_scaling_factor": e.routed_scaling_factor,
            "held_first": first, "n_routed_experts": held,
            "rope_scaling": {
                "factor": y.factor,
                "original_max_position_embeddings": y.original_max_position,
                "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                "mscale_all_dim": y.mscale_all_dim}}


def _params(cfg, seed):
    """Seeded weights, with a correction bias large enough at this size to
    move some choices (init leaves it zero, as the published model starts)."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    bias = params["layers"]["moe"]["score_bias"]
    params["layers"]["moe"]["score_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 100), bias.shape, bias.dtype)
    return params


def _tokens(cfg, b, s, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (b, s)), jnp.int32)


# Float32 throughout, the same operations: the program and the reference
# differ only in the order of sums (and the absorbed form's association in
# decode), ~1e-6 relative at these widths; logits are O(1).
F32_TOL = 2e-4


def test_smoke_config_holds_every_piece():
    cfg = _cfg()
    assert cfg.mla is not None and cfg.rope_scaling is not None
    assert cfg.first_k_dense >= 1 and cfg.moe.n_shared_experts == 1
    assert cfg.moe.n_groups > 1 and cfg.moe.held == (HELD_FIRST, N_HELD)
    assert cfg.moe.held[1] < cfg.moe.n_experts


@pytest.mark.parametrize("seed", [0, 1])
def test_forward_matches_reference(seed):
    cfg = _cfg()
    params = _params(cfg, seed)
    toks = _tokens(cfg, 2, 12, seed)
    got, _ = jax.jit(lambda p, t: lm_forward(cfg, p, t))(params, toks)
    want = ref.logits(_reference_config(cfg), params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=F32_TOL, atol=F32_TOL)


def test_prefill_then_decode_through_the_latent_cache_matches_reference():
    """The serve path: prefill (one scan of the absorbed decode step over
    the prompt), then decode steps reading the latent cache, each step's
    logits against the reference's full forward pass at that position."""
    cfg = _cfg()
    params = _params(cfg, 3)
    model = build_model(cfg)
    b, plen, s = 2, 5, 11
    toks = _tokens(cfg, b, s, 3)
    want = np.asarray(ref.logits(_reference_config(cfg), params, toks))
    tok, logit, cache = jax.jit(make_prefill_step(model))(
        params, model.init_cache(b, s), toks[:, :plen])
    np.testing.assert_array_equal(np.asarray(tok[:, 0]),
                                  want[:, plen - 1].argmax(-1))
    np.testing.assert_allclose(
        np.asarray(logit[:, 0]), want[:, plen - 1].max(-1), rtol=F32_TOL,
        atol=F32_TOL)
    step = jax.jit(model.decode_step)
    for t in range(plen, s):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t],
                                   rtol=F32_TOL, atol=F32_TOL)


def test_bf16_decode_stays_near_the_reference():
    """At the served precision (bf16 weights and activations) the decode
    path's logits stay near the float32 reference: most positions within
    bf16's rounding (0.1 at these O(1) logits), and greedy picks agree on
    nearly all; a router near-tie may flip an expert at a few."""
    cfg = _cfg().replace(param_dtype="bfloat16", compute_dtype="bfloat16")
    params = _params(cfg, 4)
    model = build_model(cfg)
    b, s = 4, 12
    toks = _tokens(cfg, b, s, 4)
    want = np.asarray(ref.logits(_reference_config(cfg), params, toks))
    cache, step, got = model.init_cache(b, s), jax.jit(model.decode_step), []
    for t in range(s):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        got.append(np.asarray(logits[:, 0]))
    got = np.stack(got, 1)
    err = np.abs(got - want).max(-1)                     # [B, S]
    assert (err < 0.1).mean() > 0.8, err
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9


def test_yarn_frequencies_and_softmax_scale_match_the_closed_form():
    """DeepSeek-V3's published rope: 64 rope dims, base 10000, factor 40,
    original length 4096, beta_fast 32, beta_slow 1. The correction range
    is dims [floor(d(32)), ceil(d(1))] = [10, 23] with
    d(r) = 64 ln(4096 / (2 pi r)) / (2 ln 10000); below it the plain
    frequency, above it the frequency / 40, a linear ramp between. The
    softmax scale is 192 ** -0.5 * (0.1 ln 40 + 1) ** 2."""
    cfg = get_config("deepseek_v3")
    i = np.arange(32)
    plain = 10000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = plain / 40 * ramp + plain * (1 - ramp)
    np.testing.assert_allclose(np.asarray(mla.rope_freqs(cfg)), want,
                               rtol=1e-6)
    c = _reference_config(cfg)
    np.testing.assert_allclose(np.asarray(ref.yarn_freqs(c)), want, rtol=1e-6)
    scale = 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2
    assert mla.softmax_scale(cfg) == pytest.approx(scale, rel=1e-12)
    assert ref.softmax_scale(c) == pytest.approx(scale, rel=1e-12)


def test_correction_bias_moves_the_choice_not_the_weights(rng):
    """The bias changes which experts are picked, and the picked experts'
    weights are their own (unbiased) scores, renormalized and scaled."""
    mc = get_config("deepseek_v3").moe
    logits = jnp.asarray(rng.normal(size=(512, mc.n_experts)), jnp.float32)
    bias = jnp.asarray(0.02 * rng.normal(size=(mc.n_experts,)), jnp.float32)
    plain_idx, plain_w, _ = moe.route(mc, logits)
    idx, w, _ = moe.route(mc, logits, bias)
    changed = (jnp.sort(idx, -1) != jnp.sort(plain_idx, -1)).any(-1)
    assert 0 < int(changed.sum()) < logits.shape[0]
    for i, wt in ((idx, w), (plain_idx, plain_w)):
        s = jnp.take_along_axis(jax.nn.sigmoid(logits), i, -1)
        np.testing.assert_allclose(
            np.asarray(wt), np.asarray(mc.routed_scaling_factor
                                       * s / s.sum(-1, keepdims=True)),
            rtol=1e-6)
    # group-limited: every pick lies in one of the topk_group best groups
    groups = np.asarray(idx) // (mc.n_experts // mc.n_groups)
    assert max(len(set(row)) for row in groups) <= mc.topk_group


def test_held_expert_layer_drops_no_token(rng):
    """A row's output is its own alone: the held-expert layer of a batch
    equals the layer run on each row by itself, even with every token
    routed to the same held experts."""
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg, 5)["layers"]["moe"])
    p = dict(p, score_bias=p["score_bias"].at[HELD_FIRST:HELD_FIRST + 4]
             .add(10.0))
    x = jnp.asarray(rng.normal(size=(3, 16, cfg.d_model)), jnp.float32)
    layer = jax.jit(lambda x: moe.moe_ffn(cfg, p, x)[0])
    whole = np.asarray(layer(x))
    for b in range(3):
        np.testing.assert_allclose(whole[b], np.asarray(layer(x[b:b + 1]))[0],
                                   rtol=1e-5, atol=1e-5)
    alone = np.asarray(layer(x[1:2, 7:8]))[0, 0]
    np.testing.assert_allclose(whole[1, 7], alone, rtol=1e-5, atol=1e-5)


def test_held_expert_shares_add_up_to_the_whole_layer(rng):
    """Over the eight shares of E/8 experts each (one chip's each, as in
    expert parallelism), the parts add up to the uncut layer, with the
    shared expert, which every chip computes alike, counted once."""
    whole_cfg = _cfg(held=False)
    e = whole_cfg.moe.n_experts
    p = jax.tree.map(lambda a: a[0], _params(whole_cfg, 6)["layers"]["moe"])
    x = jnp.asarray(rng.normal(size=(2, 8, whole_cfg.d_model)), jnp.float32)
    whole = moe.moe_ffn(whole_cfg, p, x)[0]
    shared = moe.mlp(whole_cfg, p["shared"], x)
    size = e // 8
    parts = []
    for first in range(0, e, size):
        cfg = whole_cfg.replace(moe=dataclasses.replace(
            whole_cfg.moe, held_first=first, n_held=size))
        share = dict(p, **{k: p[k][first:first + size]
                           for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe.moe_ffn(cfg, share, x)[0] - shared)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert sum(bool(jnp.abs(part).max() > 0) for part in parts) > 1


def test_share_of_the_reference_matches_the_program_share(rng):
    """The reference gives the same held share: its MoE sublayer with
    experts [4, 12) held equals the program's."""
    cfg = _cfg()
    p = jax.tree.map(lambda a: a[0], _params(cfg, 7)["layers"]["moe"])
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32)
    got = moe.moe_ffn(cfg, p, x)[0]
    want = ref.experts(_reference_config(cfg), p, x, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=F32_TOL, atol=F32_TOL)

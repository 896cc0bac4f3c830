"""Per-architecture smoke tests (reduced configs) + decode/train consistency.

The assignment requires: for each architecture, instantiate a REDUCED config
of the same family and run one forward/train step on CPU asserting output
shapes + no NaNs. Decode consistency additionally proves the serve path
agrees with teacher forcing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import make_train_state, make_train_step
from repro.models import build_model
from repro.optim import OptConfig

ARCHS = [a for a in ARCH_IDS]


def _batch(cfg, rng, b=2, s=32):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.frontend.n_tokens, cfg.d_model)),
            jnp.bfloat16)
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(b, cfg.frontend.n_tokens, cfg.frontend.embed_dim)),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    state = make_train_state(model, jax.random.PRNGKey(0))
    batch = _batch(cfg, rng)
    step = jax.jit(make_train_step(model, OptConfig(warmup_steps=2,
                                                    total_steps=10)))
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert int(new_state["step"]) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        state["params"], new_state["params"])
    assert max(jax.tree.leaves(moved)) > 0, arch
    # second step still finite
    _, metrics2 = step(new_state, batch)
    assert np.isfinite(float(metrics2["loss"])), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_logits_shape_and_finite(arch, rng):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 16
    batch = _batch(cfg, rng, b, s)
    if cfg.family == "encdec":
        from repro.models.encdec import decode_train, encode

        logits = decode_train(cfg, params, batch["tokens"],
                              encode(cfg, params, batch["frames"]))
    else:
        from repro.models.lm import lm_forward

        extra = batch.get("patches")
        logits, _ = lm_forward(cfg, params, batch["tokens"],
                               extra_embed=extra,
                               prefix_len=extra.shape[1] if extra is not None
                               else None)
        if extra is not None:
            assert logits.shape == (b, s + cfg.frontend.n_tokens,
                                    cfg.vocab_size)
            logits = logits[:, extra.shape[1]:]
    assert logits.shape == (b, s, cfg.vocab_size), arch
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ["granite_8b", "qwen3_14b", "rwkv6_1p6b",
                                  "zamba2_1p2b", "llama4_maverick_400b_a17b"])
def test_decode_matches_teacher_forcing(arch, rng):
    """Greedy decode over a forced token stream must reproduce the training
    forward's logits step by step (same params, same tokens)."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    b, s = 2, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)

    from repro.models.lm import lm_forward

    ref_logits, _ = lm_forward(cfg, params, toks)

    cache = model.init_cache(b, s)
    step = jax.jit(model.decode_step)
    got = []
    for t in range(s):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref_logits, np.float32),
        rtol=0.15, atol=0.15)  # bf16 accumulation differences
    # argmax agreement is the functional bar
    agree = (np.argmax(np.asarray(got), -1)
             == np.argmax(np.asarray(ref_logits), -1)).mean()
    assert agree > 0.9, (arch, agree)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("b,t,h,kv,dh", [(2, 9, 4, 4, 96), (3, 17, 8, 2, 128),
                                         (1, 5, 6, 3, 16)],
                         ids=["mha96", "gqa128", "gqa16"])
def test_attention_decode_over_the_merged_axis_matches_attention_full(
        b, t, h, kv, dh, dtype, rng):
    """The decode read over a lane-dense cache ([B,T,Kv*Dh]) gives the
    split read's output (``attention_full`` over [B,T,Kv,Dh]) to within
    one rounding of the output dtype; keys from ``kv_len`` on are masked."""
    from repro.models.attention import attention_decode, attention_full

    q, k, v = (jnp.asarray(rng.normal(size=s), dtype) for s in
               [(b, 1, h, dh), (b, t, kv, dh), (b, t, kv, dh)])
    kv_len = jnp.int32(t - 2)
    want = attention_full(q, k[:, :t - 2], v[:, :t - 2], causal=False)
    got = jax.jit(attention_decode)(q, k.reshape(b, t, kv * dh),
                                    v.reshape(b, t, kv * dh), kv_len)
    assert got.shape == want.shape and got.dtype == want.dtype
    ulp = 2 ** -7 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=ulp, atol=ulp)
    # the masked keys do not count: changing them changes nothing
    noise = jnp.asarray(rng.normal(size=(b, 2, kv * dh)), dtype)
    k2 = k.reshape(b, t, kv * dh).at[:, t - 2:].set(noise)
    again = jax.jit(attention_decode)(q, k2, v.reshape(b, t, kv * dh), kv_len)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("arch,head_dim", [("phi3_mini_3p8b", 96),
                                           ("llama3_405b", 128)],
                         ids=["mha96", "gqa128"])
def test_greedy_decode_through_the_lane_dense_cache_matches_forward(
        arch, head_dim, dtype, rng):
    """Prefill then greedy decode through the lane-dense K/V cache
    ([L,B,T,Kv*Dh]) serves, at every step, the full-sequence forward's
    logit of the served token, at a head width that is no multiple of 128
    (phi3's 96) and at 128; in float32 each served token is the forward's
    argmax."""
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models.lm import lm_forward

    cfg = get_config(arch, smoke=True).replace(head_dim=head_dim,
                                               compute_dtype=dtype)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    b, plen, gen = 2, 5, 6
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, plen)),
                          jnp.int32)
    cache = model.init_cache(b, plen + gen)
    for leaf in cache["layers"]["cache"].values():
        assert leaf.shape == (cfg.n_layers, b, plen + gen,
                              cfg.n_kv_heads * head_dim)
    tok, logit, cache = jax.jit(make_prefill_step(model))(params, cache,
                                                          prompts)
    step = jax.jit(make_serve_step(model))
    toks, logits = [tok], [logit]
    for t in range(plen, plen + gen - 1):
        tok, logit, cache = step(params, cache, tok, jnp.int32(t))
        toks.append(tok)
        logits.append(logit)
    toks = jnp.concatenate(toks, 1)                               # [B, gen]
    ref, _ = lm_forward(cfg, params,
                        jnp.concatenate([prompts, toks[:, :-1]], 1))
    ref = np.asarray(ref[:, plen - 1:], np.float32)               # [B, gen, V]
    served = np.take_along_axis(ref, np.asarray(toks)[..., None], -1)[..., 0]
    tol = 0.05 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(jnp.concatenate(logits, 1)),
                               served, rtol=tol, atol=tol)
    if dtype == "float32":
        np.testing.assert_array_equal(ref.argmax(-1), np.asarray(toks))


@pytest.mark.parametrize("arch", ["relic_tiny", "arctic_480b",
                                  "zamba2_1p2b"],
                         ids=["dense", "moe", "hybrid"])
def test_decode_step_writes_only_row_pos_of_each_kv_cache(arch, rng):
    """One decode step changes row ``pos`` of every layer's K and V and
    leaves every other cache element bit-equal to the input cache (the
    hybrid's K/V are its shared attention block's)."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    b, t, pos = 2, 8, 5
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        model.init_cache(b, t))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    _, new = jax.jit(model.decode_step)(params, cache, toks, jnp.int32(pos))

    pairs = [(cache["layers"]["cache"], new["layers"]["cache"])]
    if "shared_attn" in cache:
        pairs.append((cache["shared_attn"], new["shared_attn"]))
    rest = np.arange(t) != pos
    checked = 0
    for old_c, new_c in pairs:
        for name in ("k", "v"):
            if name not in old_c:
                continue
            before = np.asarray(old_c[name], np.float32)   # [L,B,T,Kv*Dh]
            after = np.asarray(new_c[name], np.float32)
            np.testing.assert_array_equal(after[:, :, rest],
                                          before[:, :, rest])
            # every layer's row pos is rewritten
            assert (after[:, :, pos] != before[:, :, pos]).any(
                axis=(1, 2)).all()
            checked += 1
    assert checked == 2, arch
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new) == jax.tree.map(
        lambda a: (a.shape, a.dtype), cache)


def test_encdec_decode_matches_teacher_forcing(rng):
    cfg = get_config("whisper_large_v3", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    b, s = 2, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    frames = jnp.asarray(
        rng.normal(size=(b, cfg.frontend.n_tokens, cfg.d_model)), jnp.bfloat16)

    from repro.models.encdec import decode_train, encode, prefill_cross_cache

    enc_out = encode(cfg, params, frames)
    ref_logits = decode_train(cfg, params, toks, enc_out)

    cache = prefill_cross_cache(cfg, params, model.init_cache(b, s), enc_out)
    step = jax.jit(model.decode_step)
    got = []
    for t in range(s):
        logits, cache = step(params, cache, toks[:, t:t + 1], jnp.int32(t))
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    agree = (np.argmax(np.asarray(got), -1)
             == np.argmax(np.asarray(ref_logits), -1)).mean()
    assert agree > 0.9, agree


def test_moe_routing_respects_capacity(rng):
    """The MoE layer has no capacity: however many tokens of a batch pick
    the same experts, none is dropped. A row's output is the row's own
    alone, so it equals the layer run on that row by itself, even when
    every token of the batch routes to the same experts."""
    import dataclasses

    from repro.models.moe import init_moe, moe_ffn

    cfg = get_config("arctic_480b", smoke=True).replace(
        compute_dtype="float32", param_dtype="float32")
    p = init_moe(cfg, jax.random.PRNGKey(5))
    # one router column far above the rest: every token picks expert 3
    p = dict(p, router=p["router"].at[:, 3].add(50.0))
    x = jnp.asarray(rng.normal(size=(2, 64, cfg.d_model)), jnp.float32)
    layer = jax.jit(lambda x: moe_ffn(cfg, p, x)[0])
    whole = np.asarray(layer(x))
    for b, t in [(0, 0), (0, 63), (1, 17)]:
        alone = np.asarray(layer(x[b:b + 1, t:t + 1]))
        np.testing.assert_allclose(whole[b, t], alone[0, 0], rtol=1e-5,
                                   atol=1e-5)
    # dropping would leave the routed part zero for late tokens
    mc = dataclasses.replace(cfg.moe, dense_residual=False)
    routed = jax.jit(lambda x: moe_ffn(cfg.replace(moe=mc),
                                       {k: v for k, v in p.items()
                                        if k != "shared"}, x)[0])(x)
    assert bool((jnp.abs(routed[:, -8:]).max(-1) > 0).all())


def test_rwkv_chunked_matches_stepwise(rng):
    """Chunked-parallel WKV == sequential decode recurrence over a stream."""
    from repro.models.rwkv6 import wkv6_chunked, wkv6_step

    b, t, h, k = 1, 32, 2, 8
    r = jnp.asarray(rng.normal(size=(b, t, h, k)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, t, h, k)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, k)), jnp.float32)
    lw = -jnp.exp(jnp.asarray(rng.normal(size=(b, t, h, k)), jnp.float32) - 1)
    u = jnp.asarray(rng.normal(size=(h, k)), jnp.float32)
    state0 = jnp.zeros((b, h, k, k), jnp.float32)
    out_c, state_c = wkv6_chunked(r, kk, v, lw, u, state0, 8)
    state = state0
    outs = []
    for i in range(t):
        o, state = wkv6_step(r[:, i], kk[:, i], v[:, i], lw[:, i], u, state)
        outs.append(o)
    out_s = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(state_c), np.asarray(state),
                               rtol=1e-4, atol=1e-4)


def test_mamba2_chunked_matches_stepwise(rng):
    from repro.models.mamba2 import ssd_chunked, ssd_step

    b, t, h, p, n = 1, 32, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32)
    a = -jnp.abs(jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32))
    bb = jnp.asarray(rng.normal(size=(b, t, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, t, n)), jnp.float32)
    state0 = jnp.zeros((b, h, p, n), jnp.float32)
    y_c, s_c = ssd_chunked(x, a, bb, cc, state0, 8)
    state = state0
    ys = []
    for i in range(t):
        y, state = ssd_step(x[:, i], a[:, i], bb[:, i], cc[:, i], state)
        ys.append(y)
    y_s = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(state),
                               rtol=1e-4, atol=1e-4)

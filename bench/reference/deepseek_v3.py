"""Plain reference of DeepSeek-V3 (arXiv:2412.19437) for one chip's share of
an expert-parallel deployment, in float32.

Written from the published description and configuration: RMSNorm;
multi-head latent attention in its expanded form (queries through the
``q_lora_rank`` latent, keys and values up-projected from the normed
``kv_lora_rank`` latent, a roped key part shared by the heads) with YaRN
rope; the first ``first_k_dense_replace`` layers with a SwiGLU MLP, the
rest with the mixture of experts: sigmoid scores over all routed experts,
the correction bias added for the choice only, the best ``topk_group`` of
``n_group`` groups (a group scored by its two best biased scores), top
``num_experts_per_tok`` of what they hold, the chosen scores renormalized
and scaled by ``routed_scaling_factor``, plus the shared expert; untied
output head. It imports nothing of the program and reads the weights the
benchmark made (``bench/weights_mla_moe.py``).

Departures, each also the program's:
- rope is the rotate-half form; the published code pairs interleaved
  dimensions, a fixed permutation of the rope rows of ``q_b`` and columns
  of ``kv_a``, which seeded weights do not see;
- the published ``q_b`` and ``kv_b`` are each held as two matrices, their
  rows split by output (``wq_nope``/``wq_pe``; ``wk_b``, the key part, and
  ``wv_b``, the value), head-major, as the program holds them;
- of the routed experts only those this chip holds
  (``[held_first, held_first + n_routed_experts)``) add their part; what
  the others would add is another chip's work and is left out;
- the multi-token-prediction module is not run (greedy serving without
  speculation never runs it).

Every projection runs at ``Precision.HIGHEST`` through
``bench.reference.lm.mm``, with its ``mode`` controls (``int8``, ``fp8``:
both operands rounded, one scale per tensor). The held experts run densely:
each held expert on every token, weighted by the token's routing weight
for it (zero where it was not chosen). The forward pass runs layer by
layer over blocks of rows, and the output head over blocks of the
vocabulary, so that it fits beside the served weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.lm import HIGHEST, _quantize, embed, mm, rms_norm


def yarn_freqs(c: dict) -> jax.Array:
    """Rope frequencies of the ``qk_rope_head_dim`` columns after YaRN."""
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    rs = c["rope_scaling"]
    freqs = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / rs["factor"] * ramp + freqs * (1.0 - ramp)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 \
        * mscale * mscale


def rope(x, freqs, offset=0):
    """x: [B, S, H, r] at positions offset.., rotate-half form."""
    ang = (offset + jnp.arange(x.shape[1], dtype=jnp.float32))[:, None] \
        * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(c: dict, a: dict, x, mode: str):
    """Causal latent attention, expanded, on x [B, S, D] (normed)."""
    eps, kvl = c["rms_norm_eps"], c["kv_lora_rank"]
    cq = rms_norm(mm("bsd,dc->bsc", x, a["wq_a"], mode), a["q_norm"]["scale"],
                  eps)
    kv = mm("bsd,dc->bsc", x, a["wkv_a"], mode)
    c_kv = rms_norm(kv[..., :kvl], a["kv_norm"]["scale"], eps)
    k_nope = mm("btc,hnc->bthn", c_kv, a["wk_b"], mode)
    v = mm("btc,hcv->bthv", c_kv, a["wv_b"], mode)
    freqs = yarn_freqs(c)
    q = jnp.concatenate([mm("bsc,hnc->bshn", cq, a["wq_nope"], mode),
                         rope(mm("bsc,hrc->bshr", cq, a["wq_pe"], mode),
                              freqs)], -1)
    k_pe = rope(kv[..., None, kvl:], freqs)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + k_pe.shape[-1:])], -1)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k, precision=HIGHEST) \
        * softmax_scale(c)
    n = x.shape[1]
    s = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :], s,
                  -jnp.inf)
    o = jnp.einsum("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return mm("bqhv,hvd->bqd", o, a["wo"], mode)


def swiglu(m: dict, x, mode: str):
    g = jax.nn.silu(mm("bsd,df->bsf", x, m["w_gate"], mode))
    u = mm("bsd,df->bsf", x, m["w_up"], mode)
    return mm("bsf,fd->bsd", g * u, m["w_down"], mode)


def routing(c: dict, moe: dict, x, mode: str):
    """Weights [B, S, E] of every routed expert for each token: the chosen
    experts' renormalized, scaled scores, zero elsewhere."""
    logits = mm("bsd,de->bse", x, moe["router"], mode)
    scores = jax.nn.sigmoid(logits)
    biased = scores + moe["score_bias"].astype(jnp.float32)
    b, s, e = scores.shape
    g = c["n_group"]
    grouped = biased.reshape(b, s, g, e // g)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [B, S, G]
    kth = jax.lax.top_k(group_score, c["topk_group"])[0][..., -1:]
    biased = jnp.where((group_score >= kth)[..., None], grouped,
                       -jnp.inf).reshape(b, s, e)
    kth = jax.lax.top_k(biased, c["num_experts_per_tok"])[0][..., -1:]
    chosen = biased >= kth
    w = jnp.where(chosen, scores, 0.0)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * c["routed_scaling_factor"]


def experts(c: dict, moe: dict, x, mode: str):
    """The held experts' part plus the shared expert, on x [B, S, D]."""
    first, held = c["held_first"], c["n_routed_experts"]
    w = routing(c, moe, x, mode)[..., first:first + held]       # [B, S, e]
    g = jax.nn.silu(mm("bsd,edf->bsef", x, moe["w_gate"], mode))
    u = mm("bsd,edf->bsef", x, moe["w_up"], mode)
    y = mm("bsef,efd->bsed", g * u, moe["w_down"], mode)
    routed = jnp.einsum("bse,bsed->bsd", w, y, precision=HIGHEST)
    return routed + swiglu(moe["shared"], x, mode)


def layer(c: dict, lp: dict, x, mode: str = "f32"):
    """One block on the residual stream x [B, S, D] (float32)."""
    eps = c["rms_norm_eps"]
    x = x + attention(c, lp["attn"], rms_norm(x, lp["ln1"]["scale"], eps),
                      mode)
    h = rms_norm(x, lp["ln2"]["scale"], eps)
    if "moe" in lp:
        return x + experts(c, lp["moe"], h, mode)
    return x + swiglu(lp["mlp"], h, mode)


_KEYS = ("rms_norm_eps", "qk_nope_head_dim", "qk_rope_head_dim",
         "kv_lora_rank", "rope_theta", "n_group", "topk_group",
         "num_experts_per_tok", "routed_scaling_factor", "held_first",
         "n_routed_experts")


def _items(c: dict) -> tuple:
    rs = tuple(sorted(c["rope_scaling"].items()))
    return tuple((k, c[k]) for k in _KEYS) + (("rope_scaling", rs),)


def _config(items) -> dict:
    c = dict(items)
    c["rope_scaling"] = dict(c["rope_scaling"])
    return c


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _layer_jit(stack, i, x, items, mode):
    """Layer ``i`` of a stacked layer tree, sliced inside the program so
    that no copy of it outlives the call."""
    lp = jax.tree.map(lambda a: a[i], stack)
    return layer(_config(items), lp, x, mode)


def _stacks(params):
    """(stack name, layer index) of every layer, in order."""
    out = []
    for name in ("dense_layers", "layers"):
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            out += [(name, i) for i in range(n)]
    return out


def hidden(c: dict, params, tokens, mode: str = "f32",
           rows: int = 4) -> jax.Array:
    """The final-normed hidden states [B, S, D] (float32) of tokens
    [B, S], layer by layer over blocks of ``rows`` rows; each layer is
    finished before the next starts, so one layer's float32 temporaries
    are live at a time."""
    items = _items(c)
    x = jax.jit(embed)(params, tokens)
    for name, i in _stacks(params):
        x = jax.block_until_ready(jnp.concatenate(
            [_layer_jit(params[name], i, x[r:r + rows], items, mode)
             for r in range(0, x.shape[0], rows)], 0))
    return jax.jit(rms_norm, static_argnums=2)(
        x, params["final_norm"]["scale"], c["rms_norm_eps"])


def _round(x, amax, mode: str):
    """``_quantize`` of a block of a tensor whose largest magnitude is
    ``amax``: the whole tensor's scale."""
    if mode == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("size", "mode"))
def _head_block(h, w, amax, chosen, start, size, mode):
    """One vocabulary block, columns [start, start + size) of the head
    ``w``: (max, argmax, logit of ``chosen`` where it lies in the block,
    else -inf)."""
    w = jax.lax.dynamic_slice_in_dim(w, start, size, 1).astype(jnp.float32)
    if mode != "f32":
        w = _round(w, amax, mode)
    lg = jnp.einsum("bsd,dv->bsv", h, w, precision=HIGHEST)
    local = chosen - start
    inside = (local >= 0) & (local < size)
    picked = jnp.take_along_axis(
        lg, jnp.clip(local, 0, size - 1)[..., None], -1)[..., 0]
    return (lg.max(-1), lg.argmax(-1) + start,
            jnp.where(inside, picked, -jnp.inf))


def head(c: dict, params, h, chosen, mode: str = "f32", blocks: int = 10):
    """Over the vocabulary in at least ``blocks`` equal blocks, of h
    [B, S, D] (final-normed): (best logit [B, S], its token [B, S], the
    logit of ``chosen`` [B, S]). With ``mode`` the head's operands are
    rounded as one tensor each."""
    w = params["lm_head"]["kernel"]
    v = w.shape[1]
    size = v // next(b for b in range(blocks, v + 1) if v % b == 0)
    amax = jnp.maximum(jax.jit(lambda w: jnp.max(jnp.abs(w)))(w)
                       .astype(jnp.float32), 1e-30)
    if mode != "f32":
        h = _quantize(h, mode)
    best = arg = picked = None
    for start in range(0, v, size):
        m, a, p = _head_block(h, w, amax, chosen, jnp.int32(start), size,
                              mode)
        if best is None:
            best, arg, picked = m, a, p
        else:
            arg = jnp.where(m > best, a, arg)
            best, picked = jnp.maximum(best, m), jnp.maximum(picked, p)
    return best, arg, picked


def logits(c: dict, params, tokens, mode: str = "f32") -> jax.Array:
    """Logits [B, S, V] in float32 of tokens [B, S] (small sizes)."""
    h = hidden(c, params, tokens, mode)
    w = params["lm_head"]["kernel"]
    if mode == "f32":
        return jnp.einsum("bsd,dv->bsv", h, w.astype(jnp.float32),
                          precision=HIGHEST)
    return mm("bsd,dv->bsv", h, w, mode)

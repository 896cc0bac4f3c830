"""Multi-head latent attention (DeepSeek-V2 arXiv:2405.04434 §2.1; V3
arXiv:2412.19437 §2.1.1), with YaRN rope scaling (arXiv:2309.00071).

Queries pass through a low-rank latent (``wq_a``, RMSNorm, then the
published ``q_b`` as its key-part rows ``wq_nope`` and roped rows
``wq_pe``). Keys and values come from one latent per token, ``c_kv``
(``wkv_a``, RMSNorm), which the published ``kv_b`` up-projects to each
head's key part (its rows ``wk_b``, ``W_UK``) and value (``wv_b``,
``W_UV``); a second, roped key part ``k_pe`` is shared by every head. Per
token the decode cache holds only ``c_kv`` and ``k_pe``. Each weight is
held head-major, the layout its products read, so a decode step copies
none of them.

Two forms of the same attention:

- expanded (``mla_attention``, the forward pass and training): ``W_UK``
  and ``W_UV`` up-project the latent of every position;
- absorbed (``decode_mla``, decode): ``W_UK`` folds into the query and
  ``W_UV`` into the output, so scores and values run against the cached
  latent itself.

Rope is the rotate-half form on the ``qk_rope_head_dim`` columns. The
published code pairs interleaved dimensions instead; that is a fixed
permutation of the rope rows of ``q_b`` and columns of ``kv_a``.

Named scopes, so device ops can be charged to them: ``mla.proj`` (every
projection, and the cache write) and ``mla.core`` (scores, softmax and
values).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.attention import NEG_INF


def init_mla(cfg: ModelConfig, key):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    pd = L.dt(cfg.param_dtype)
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    return {
        "wq_a": L._normal(k1, (d, m.q_lora_rank), d ** -0.5, pd),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), pd)},
        "wq_nope": L._normal(k2, (h, m.qk_nope_head_dim, m.q_lora_rank),
                             m.q_lora_rank ** -0.5, pd),
        "wq_pe": L._normal(k7, (h, m.qk_rope_head_dim, m.q_lora_rank),
                           m.q_lora_rank ** -0.5, pd),
        "wkv_a": L._normal(k3, (d, m.kv_lora_rank + m.qk_rope_head_dim),
                           d ** -0.5, pd),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), pd)},
        "wk_b": L._normal(k4, (h, m.qk_nope_head_dim, m.kv_lora_rank),
                          m.kv_lora_rank ** -0.5, pd),
        "wv_b": L._normal(k6, (h, m.kv_lora_rank, m.v_head_dim),
                          m.kv_lora_rank ** -0.5, pd),
        "wo": L._normal(k5, (h, m.v_head_dim, d), (h * m.v_head_dim) ** -0.5,
                        pd),
    }


def init_cache(cfg: ModelConfig, batch: int, cache_len: int):
    m, cd = cfg.mla, L.dt(cfg.compute_dtype)
    return {"c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), cd),
            "k_pe": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), cd)}


def rope_freqs(cfg: ModelConfig) -> jax.Array:
    """Rope frequencies of the ``qk_rope_head_dim`` columns: YaRN's ramp
    between the plain frequencies (fast dimensions) and those divided by
    ``factor`` (slow dimensions)."""
    dim, theta, y = cfg.mla.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling
    freqs = L.rope_freqs(dim, theta)
    if y is None:
        return freqs

    def correction_dim(rotations):
        return dim * math.log(y.original_max_position
                              / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freqs / y.factor * ramp + freqs * (1.0 - ramp)


def softmax_scale(cfg: ModelConfig) -> float:
    """``qk_head_dim ** -0.5``, times YaRN's ``mscale ** 2``."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None:
        scale *= (0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0) ** 2
    return scale


def _project(cfg: ModelConfig, p, x, positions):
    """x [B, S, D] -> (q_nope [B,S,H,n], roped q_pe [B,S,H,r], c_kv [B,S,c],
    roped k_pe [B,S,r]), in the compute dtype."""
    m, cd = cfg.mla, L.dt(cfg.compute_dtype)
    x = x.astype(cd)
    cq = L.rms_norm_headwise(x @ p["wq_a"].astype(cd), p["q_norm"]["scale"])
    q_nope = jnp.einsum("bsc,hkc->bshk", cq, p["wq_nope"].astype(cd))
    q_pe = jnp.einsum("bsc,hkc->bshk", cq, p["wq_pe"].astype(cd))
    kv = x @ p["wkv_a"].astype(cd)
    c_kv = L.rms_norm_headwise(kv[..., :m.kv_lora_rank], p["kv_norm"]["scale"])
    k_pe = kv[..., m.kv_lora_rank:]
    freqs = rope_freqs(cfg)
    q_pe = L.apply_rope(q_pe, positions, cfg.rope_theta, freqs)
    k_pe = L.apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta,
                        freqs)[:, :, 0, :]
    return q_nope, q_pe, c_kv, k_pe


def _softmax(scores, keep):
    return jax.nn.softmax(jnp.where(keep, scores, NEG_INF), axis=-1)


def mla_attention(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    """Causal self-attention over x [B, S, D], expanded form."""
    m, cd = cfg.mla, L.dt(cfg.compute_dtype)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    with jax.named_scope("mla.proj"):
        q_nope, q_pe, c_kv, k_pe = _project(cfg, p, x, positions)
        k_nope = jnp.einsum("btc,hnc->bthn", c_kv, p["wk_b"].astype(cd))
        v = jnp.einsum("btc,hcv->bthv", c_kv, p["wv_b"].astype(cd))
    with jax.named_scope("mla.core"):
        f32 = jnp.float32
        scores = (jnp.einsum("bqhn,bthn->bhqt", q_nope, k_nope,
                             preferred_element_type=f32)
                  + jnp.einsum("bqhr,btr->bhqt", q_pe, k_pe,
                               preferred_element_type=f32))
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        probs = _softmax(scores * softmax_scale(cfg), causal)
        o = jnp.einsum("bhqt,bthv->bqhv", probs.astype(cd), v)
    with jax.named_scope("mla.proj"):
        return jnp.einsum("bqhv,hvd->bqd", o, p["wo"].astype(cd))


def decode_mla(cfg: ModelConfig, p, x: jax.Array, cache: dict,
               pos: jax.Array, layer: jax.Array):
    """One-token decode, absorbed form. ``cache`` holds every layer's latent
    stacked (``c_kv [L,B,T,c]``, ``k_pe [L,B,T,r]``); only row
    ``(layer, :, pos)`` is written, and the layer's slab is read in place.
    Returns (y [B,1,D], cache)."""
    m, cd, f32 = cfg.mla, L.dt(cfg.compute_dtype), jnp.float32
    pos = pos.astype(jnp.int32)
    with jax.named_scope("mla.proj"):
        q_nope, q_pe, c_kv, k_pe = _project(
            cfg, p, x, jnp.broadcast_to(pos, (x.shape[0], 1)))
        q_lat = jnp.einsum("bshn,hnc->bshc", q_nope, p["wk_b"].astype(cd))
        cache = L.write_layer(cache, {"c_kv": c_kv, "k_pe": k_pe}, layer, pos)
        slab = L.layer_entry(cache, layer)
    with jax.named_scope("mla.core"):
        scores = (jnp.einsum("bshc,btc->bhst", q_lat, slab["c_kv"],
                             preferred_element_type=f32)
                  + jnp.einsum("bshr,btr->bhst", q_pe, slab["k_pe"],
                               preferred_element_type=f32))
        valid = jnp.arange(slab["c_kv"].shape[1]) <= pos
        probs = _softmax(scores * softmax_scale(cfg), valid)
        o_lat = jnp.einsum("bhst,btc->bshc", probs.astype(cd), slab["c_kv"])
    with jax.named_scope("mla.proj"):
        o = jnp.einsum("bshc,hcv->bshv", o_lat, p["wv_b"].astype(cd))
        return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(cd)), cache

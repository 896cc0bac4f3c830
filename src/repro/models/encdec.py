"""Whisper-style encoder–decoder backbone.

The audio frontend (two conv layers over log-mel) is a STUB per the
assignment: ``input_specs()`` provides precomputed frame embeddings
[B, T_enc, D], and the encoder consumes them directly (sinusoidal positions,
non-causal self-attention). The decoder is a standard causal stack with
cross-attention; embeddings are tied (whisper convention); layernorm + GELU,
no RoPE (learned decoder positions).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models.lm import maybe_scan
from repro.sharding import shard_act


def _init_enc_block(cfg: ModelConfig, key):
    hd = cfg.resolved_head_dim
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "attn": attn.init_attention(cfg, k1, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
        "ln2": L.init_norm(cfg, cfg.d_model),
        "mlp": L.init_mlp(cfg, k2, cfg.d_model, cfg.d_ff),
    }


def _init_dec_block(cfg: ModelConfig, key):
    hd = cfg.resolved_head_dim
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "self_attn": attn.init_attention(cfg, k1, cfg.d_model, cfg.n_heads,
                                         cfg.n_kv_heads, hd),
        "lnx": L.init_norm(cfg, cfg.d_model),
        "cross_attn": attn.init_attention(cfg, k2, cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, hd),
        "ln2": L.init_norm(cfg, cfg.d_model),
        "mlp": L.init_mlp(cfg, k3, cfg.d_model, cfg.d_ff),
    }


def init_encdec(cfg: ModelConfig, key):
    ke, k1, k2, kp = jax.random.split(key, 4)
    enc_keys = jax.random.split(k1, cfg.enc_layers)
    dec_keys = jax.random.split(k2, cfg.n_layers)
    return {
        "embed": L.init_embed(cfg, ke, cfg.vocab_size, cfg.d_model),
        "pos_embed": L._normal(kp, (cfg.max_seq, cfg.d_model), 0.01,
                               L.dt(cfg.param_dtype)),
        "enc_layers": jax.vmap(lambda k: _init_enc_block(cfg, k))(enc_keys),
        "enc_norm": L.init_norm(cfg, cfg.d_model),
        "dec_layers": jax.vmap(lambda k: _init_dec_block(cfg, k))(dec_keys),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }


def _enc_block(cfg, p, x):
    x = x + attn.self_attention(cfg, p["attn"], L.norm(cfg, p["ln1"], x),
                                causal=False)
    x = x + L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
    return x


def _dec_block(cfg, p, x, enc_out):
    x = x + attn.self_attention(cfg, p["self_attn"], L.norm(cfg, p["ln1"], x),
                                causal=True)
    x = x + attn.cross_attention(cfg, p["cross_attn"], L.norm(cfg, p["lnx"], x),
                                 enc_out)
    x = x + L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
    return x


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


def encode(cfg: ModelConfig, params, frames: jax.Array) -> jax.Array:
    """frames: [B,T_enc,D] (stubbed frontend output)."""
    cd = L.dt(cfg.compute_dtype)
    x = frames.astype(cd) + L.sinusoidal_positions(frames.shape[1],
                                                   cfg.d_model).astype(cd)
    x = shard_act(x, "batch", None, "model", kind="resid")
    blk = _remat(cfg, functools.partial(_enc_block, cfg))

    def body(x, lp):
        return blk(lp, x), None

    x, _ = maybe_scan(cfg, body, x, params["enc_layers"])
    return L.norm(cfg, params["enc_norm"], x)


def decode_train(cfg: ModelConfig, params, tokens: jax.Array,
                 enc_out: jax.Array) -> jax.Array:
    x = L.embed(cfg, params["embed"], tokens)
    s = tokens.shape[1]
    x = x + params["pos_embed"][:s].astype(x.dtype)[None]
    x = shard_act(x, "batch", None, "model", kind="resid")
    blk = _remat(cfg, functools.partial(_dec_block, cfg))

    def body(x, lp):
        return blk(lp, x, enc_out), None

    x, _ = maybe_scan(cfg, body, x, params["dec_layers"])
    x = L.norm(cfg, params["final_norm"], x)
    return L.unembed(cfg, None, x, tied_table=params["embed"]["table"])


def encdec_loss(cfg: ModelConfig, params, batch: dict):
    """batch: {frames [B,T,D], tokens [B,S], labels [B,S], mask?}."""
    enc_out = encode(cfg, params, batch["frames"])
    logits = decode_train(cfg, params, batch["tokens"], enc_out)
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce, {"loss": ce, "ce": ce, "aux": jnp.zeros(()), "tokens": mask.sum()}


# --------------------------------------------------------------------- decode

def init_encdec_cache(cfg: ModelConfig, batch: int, cache_len: int):
    """Self-attn KV caches + precomputed cross-attn K/V (filled at prefill)."""
    hd = cfg.resolved_head_dim
    cd = L.dt(cfg.compute_dtype)
    enc_len = cfg.frontend.n_tokens if cfg.frontend else cfg.max_seq

    def one(_):
        return {
            **attn.init_decode_cache(cfg, batch, cache_len, cfg.n_kv_heads,
                                     hd),
            "xk": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), cd),
            "xv": jnp.zeros((batch, enc_len, cfg.n_kv_heads, hd), cd),
        }

    return {"layers": jax.vmap(one)(jnp.arange(cfg.n_layers))}


def prefill_cross_cache(cfg: ModelConfig, params, cache, enc_out: jax.Array):
    """Compute per-layer cross K/V from encoder output once."""
    cd = L.dt(cfg.compute_dtype)

    def per_layer(lp):
        k = jnp.einsum("btd,dhk->bthk", enc_out.astype(cd),
                       lp["cross_attn"]["wk"].astype(cd))
        v = jnp.einsum("btd,dhk->bthk", enc_out.astype(cd),
                       lp["cross_attn"]["wv"].astype(cd))
        return k, v

    xk, xv = jax.vmap(per_layer)(params["dec_layers"])
    new = dict(cache["layers"])
    new["xk"], new["xv"] = xk, xv
    return {"layers": new}


def encdec_decode_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                       pos: jax.Array):
    """One decoder token. tokens: [B,1] -> (logits, cache)."""
    x = L.embed(cfg, params["embed"], tokens)
    pe = jax.lax.dynamic_slice(params["pos_embed"], (pos, jnp.int32(0)),
                               (1, cfg.d_model))
    x = x + pe.astype(x.dtype)[None]

    def body(x, inp):
        lp, lc = inp
        y, kv = attn.decode_self_attention(
            cfg, lp["self_attn"], L.norm(cfg, lp["ln1"], x),
            {"k": lc["k"], "v": lc["v"]}, pos)
        x = x + y
        x = x + attn.decode_cross_attention(
            cfg, lp["cross_attn"], L.norm(cfg, lp["lnx"], x),
            {"xk": lc["xk"], "xv": lc["xv"]})
        x = x + L.mlp(cfg, lp["mlp"], L.norm(cfg, lp["ln2"], x))
        return x, {**kv, "xk": lc["xk"], "xv": lc["xv"]}

    x, new_layers = maybe_scan(cfg, body, x,
                               (params["dec_layers"], cache["layers"]))
    x = L.norm(cfg, params["final_norm"], x)
    logits = L.unembed(cfg, None, x, tied_table=params["embed"]["table"])
    return logits, {"layers": new_layers}

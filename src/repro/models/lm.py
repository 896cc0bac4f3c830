"""Decoder-only LM assembly: dense / MoE / RWKV-6 / Zamba2-hybrid families.

Attention is GQA, or multi-head latent attention where the config has an
``mla`` (``models/mla.py``). An MoE model may lead with ``first_k_dense``
dense layers (their own stack, ``dense_layers``); every layer, dense or
MoE, shares the one stacked decode cache.

Layers are **scanned** (`lax.scan` over stacked params) so that HLO size and
compile time are O(1) in depth — required for 126-layer dry-runs — with a
configurable remat policy. Decode carries the stacked per-layer cache
through the same scans and writes each layer's new entries into it in place.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import with_layout_constraint

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import mamba2 as m2
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models import rwkv6 as r6
from repro.sharding import shard_act


# ---------------------------------------------------------------------------
# Per-family blocks.  Every block is  (cfg, params, x, **kw) -> (x, aux)
# and has a decode twin  (cfg, params, x, cache, layer, pos) -> (x, cache)
# over the stacked cache of every layer.
# ---------------------------------------------------------------------------

def _leading(cfg: ModelConfig) -> ModelConfig:
    """The config of an MoE model's leading dense layers."""
    return cfg.replace(family="dense", moe=None)


def _init_attn(cfg: ModelConfig, key):
    if cfg.mla is not None:
        return mla.init_mla(cfg, key)
    return attn.init_attention(cfg, key, cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.resolved_head_dim)


def _attn(cfg: ModelConfig, p, x, prefix_len=None):
    if cfg.mla is not None:
        return mla.mla_attention(cfg, p, x)
    return attn.self_attention(cfg, p, x, causal=True, prefix_len=prefix_len)


def _attn_decode(cfg: ModelConfig, p, x, cache, pos, layer):
    if cfg.mla is not None:
        return mla.decode_mla(cfg, p, x, cache, pos, layer)
    return attn.decode_self_attention(cfg, p, x, cache, pos, layer)


def init_block(cfg: ModelConfig, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.family in ("dense", "vlm"):
        return {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "attn": _init_attn(cfg, k1),
            "ln2": L.init_norm(cfg, cfg.d_model),
            "mlp": L.init_mlp(cfg, k2, cfg.d_model, cfg.d_ff),
        }
    if cfg.family == "moe":
        return {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "attn": _init_attn(cfg, k1),
            "ln2": L.init_norm(cfg, cfg.d_model),
            "moe": moe_mod.init_moe(cfg, k2),
        }
    if cfg.family == "ssm":  # rwkv6
        return {
            "ln1": L.init_norm(cfg, cfg.d_model),
            "rwkv": r6.init_rwkv_time_mix(cfg, k1),
            "ln2": L.init_norm(cfg, cfg.d_model),
            "cmix": r6.init_rwkv_channel_mix(cfg, k2),
        }
    if cfg.family == "hybrid":  # zamba2 mamba layer
        return {
            "ln": L.init_norm(cfg, cfg.d_model),
            "ssm": m2.init_mamba2(cfg, k1),
        }
    raise ValueError(cfg.family)


def init_shared_attn(cfg: ModelConfig, key):
    """Zamba2's shared transformer block (one param set, applied periodically)."""
    hd = cfg.resolved_head_dim
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.init_norm(cfg, cfg.d_model),
        "attn": attn.init_attention(cfg, k1, cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, hd),
        "ln2": L.init_norm(cfg, cfg.d_model),
        "mlp": L.init_mlp(cfg, k2, cfg.d_model, cfg.d_ff),
    }


def block_fwd(cfg: ModelConfig, p, x, *, prefix_len=None):
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "vlm"):
        x = x + _attn(cfg, p["attn"], L.norm(cfg, p["ln1"], x), prefix_len)
        x = x + L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
    elif cfg.family == "moe":
        x = x + _attn(cfg, p["attn"], L.norm(cfg, p["ln1"], x))
        y, aux = moe_mod.moe_ffn(cfg, p["moe"], L.norm(cfg, p["ln2"], x))
        x = x + y
    elif cfg.family == "ssm":
        x = x + r6.rwkv_time_mix(cfg, p["rwkv"], L.norm(cfg, p["ln1"], x))
        x = x + r6.rwkv_channel_mix(cfg, p["cmix"], L.norm(cfg, p["ln2"], x))
    elif cfg.family == "hybrid":
        x = x + m2.mamba2_block(cfg, p["ssm"], L.norm(cfg, p["ln"], x))
    else:
        raise ValueError(cfg.family)
    return x, aux


def shared_attn_fwd(cfg: ModelConfig, p, x):
    x = x + attn.self_attention(cfg, p["attn"], L.norm(cfg, p["ln1"], x),
                                causal=True)
    x = x + L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
    return x


# --------------------------------------------------------------- decode twins

def init_block_cache(cfg: ModelConfig, batch: int, cache_len: int):
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:         # the latent c_kv and the roped k_pe
        return {"cache": mla.init_cache(cfg, batch, cache_len)}
    if cfg.family in ("dense", "vlm", "moe"):
        return {"cache": attn.init_decode_cache(cfg, batch, cache_len,
                                                cfg.n_kv_heads, hd)}
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.ssm.head_dim
        k = cfg.ssm.head_dim
        return {"cache": {
            "shift_state": jnp.zeros((batch, cfg.d_model), L.dt(cfg.compute_dtype)),
            "cmix_shift_state": jnp.zeros((batch, cfg.d_model), L.dt(cfg.compute_dtype)),
            "wkv_state": jnp.zeros((batch, h, k, k), jnp.float32),
        }}
    if cfg.family == "hybrid":
        d_inner, n_heads, conv_dim = m2._dims(cfg)
        return {"cache": {
            "conv_state": jnp.zeros((batch, cfg.ssm.conv_kernel - 1, conv_dim),
                                    L.dt(cfg.compute_dtype)),
            "ssm_state": jnp.zeros((batch, n_heads, cfg.ssm.head_dim,
                                    cfg.ssm.state_dim), jnp.float32),
        }}
    raise ValueError(cfg.family)


def block_decode(cfg: ModelConfig, p, x, cache, layer, pos):
    """One layer's decode step against the stacked cache of every layer
    (``{"cache": ...}`` with ``[L, ...]`` leaves), writing only this
    layer's new entries in place. Returns (x, cache)."""
    c = cache["cache"]
    if cfg.family in ("dense", "vlm", "moe"):
        y, c = _attn_decode(cfg, p["attn"], L.norm(cfg, p["ln1"], x), c,
                            pos, layer)
        x = x + y
        if cfg.family == "moe":
            y, _ = moe_mod.moe_ffn(cfg, p["moe"], L.norm(cfg, p["ln2"], x),
                                   layer - cfg.first_k_dense)
        else:
            y = L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
        x = x + y
    elif cfg.family == "ssm":
        s = L.layer_entry(c, layer)
        xn = L.norm(cfg, p["ln1"], x)
        y, tc = r6.rwkv_time_mix_decode(cfg, p["rwkv"], xn,
                                        {"shift_state": s["shift_state"],
                                         "wkv_state": s["wkv_state"]})
        x = x + y
        xn2 = L.norm(cfg, p["ln2"], x)
        y2 = r6.rwkv_channel_mix(cfg, p["cmix"], xn2,
                                 shift_state=s["cmix_shift_state"])
        x = x + y2
        c = L.write_layer(c, {"shift_state": tc["shift_state"],
                              "wkv_state": tc["wkv_state"],
                              "cmix_shift_state": xn2[:, 0]}, layer, pos)
    elif cfg.family == "hybrid":
        y, s = m2.mamba2_block_decode(cfg, p["ssm"], L.norm(cfg, p["ln"], x),
                                      L.layer_entry(c, layer))
        x = x + y
        c = L.write_layer(c, s, layer, pos)
    else:
        raise ValueError(cfg.family)
    return x, {"cache": c}


def shared_attn_decode(cfg: ModelConfig, p, x, kv_cache, layer, pos):
    y, kv_cache = attn.decode_self_attention(cfg, p["attn"],
                                             L.norm(cfg, p["ln1"], x),
                                             kv_cache, pos, layer)
    x = x + y
    x = x + L.mlp(cfg, p["mlp"], L.norm(cfg, p["ln2"], x))
    return x, kv_cache


# ---------------------------------------------------------------------------
# Whole-model init / forward / decode
# ---------------------------------------------------------------------------

def unrolled_scan(body, carry, xs):
    """Python-loop twin of lax.scan (scan_layers=False): exact HLO cost
    accounting for the dry-run's depth extrapolation."""
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def maybe_scan(cfg: ModelConfig, body, carry, xs):
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs)
    return unrolled_scan(body, carry, xs)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)  # "full": save only block boundaries


def _stacked_init(cfg: ModelConfig, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(cfg, k))(keys)


def init_lm(cfg: ModelConfig, key):
    ke, kl, kh, ks = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": L.init_embed(cfg, ke, cfg.vocab_size, cfg.d_model),
        "layers": _stacked_init(cfg, kl, cfg.n_layers - cfg.first_k_dense),
        "final_norm": L.init_norm(cfg, cfg.d_model),
    }
    if cfg.first_k_dense:
        params["dense_layers"] = _stacked_init(
            _leading(cfg), jax.random.fold_in(kl, 1), cfg.first_k_dense)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_unembed(cfg, kh, cfg.d_model, cfg.vocab_size)
    if cfg.family == "hybrid" and cfg.attn_every:
        params["shared_attn"] = init_shared_attn(cfg, ks)
    if cfg.family == "vlm" and cfg.frontend is not None:
        params["img_proj"] = {
            "kernel": L._normal(ks, (cfg.frontend.embed_dim, cfg.d_model),
                                cfg.frontend.embed_dim ** -0.5,
                                L.dt(cfg.param_dtype))
        }
    return params


def _scan_blocks(cfg: ModelConfig, layers_p, x, *, prefix_len=None):
    """Scan the homogeneous block stack; returns (x, aux_sum)."""
    blk = _remat(cfg, functools.partial(block_fwd, cfg, prefix_len=prefix_len))

    if not cfg.scan_layers:
        aux = jnp.zeros((), jnp.float32)
        n = jax.tree.leaves(layers_p)[0].shape[0]
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], layers_p)
            x, a = blk(lp, x)
            aux = aux + a
        return x, aux

    def body(carry, lp):
        x, aux = carry
        x, a = blk(lp, x)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), layers_p)
    return x, aux


def _hybrid_groups(cfg: ModelConfig):
    """(groups of ``attn_every`` layers, layers after them) of the main
    stack (the layers after any leading dense ones)."""
    k, n = cfg.attn_every, cfg.n_layers - cfg.first_k_dense
    full = n // k if k else 0
    return full, n - full * k


def _hybrid_fwd(cfg: ModelConfig, params, x):
    """Zamba2: groups of `attn_every` mamba layers + shared attention block."""
    full, tail = _hybrid_groups(cfg)
    k = cfg.attn_every
    layers_p = params["layers"]
    aux = jnp.zeros((), jnp.float32)
    blk = _remat(cfg, functools.partial(block_fwd, cfg))

    if full:
        shared = _remat(cfg, functools.partial(shared_attn_fwd, cfg,
                                               params["shared_attn"]))
        grouped = jax.tree.map(
            lambda a: a[: full * k].reshape(full, k, *a.shape[1:]), layers_p
        )

        def group_body(carry, gp):
            x, aux = carry

            def inner(c, lp):
                x_, a_ = c
                x_, aa = blk(lp, x_)
                return (x_, a_ + aa), None

            (x, aux), _ = maybe_scan(cfg, inner, (x, aux), gp)
            x = shared(x)
            return (x, aux), None

        (x, aux), _ = maybe_scan(cfg, group_body, (x, aux), grouped)
    if tail:
        tail_p = jax.tree.map(lambda a: a[full * k:], layers_p)
        x, a = _scan_blocks(cfg, tail_p, x)
        aux = aux + a
    return x, aux


def lm_forward(cfg: ModelConfig, params, tokens: jax.Array,
               *, extra_embed: Optional[jax.Array] = None,
               prefix_len: Optional[int] = None):
    """tokens: [B,S] -> (logits [B,S,V] f32, aux_loss)."""
    x = L.embed(cfg, params["embed"], tokens)
    if cfg.family == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)  # gemma convention
    if extra_embed is not None:
        proj = extra_embed.astype(x.dtype) @ params["img_proj"]["kernel"].astype(x.dtype)
        x = jnp.concatenate([proj, x], axis=1)
    x = shard_act(x, "batch", None, "model", kind="resid")

    if cfg.family == "hybrid":
        x, aux = _hybrid_fwd(cfg, params, x)
    else:
        if cfg.first_k_dense:
            x, _ = _scan_blocks(_leading(cfg), params["dense_layers"], x)
        x, aux = _scan_blocks(cfg, params["layers"], x, prefix_len=prefix_len)

    x = L.norm(cfg, params["final_norm"], x)
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = L.unembed(cfg, params.get("lm_head"), x, tied_table=tied)
    return logits, aux


def lm_loss(cfg: ModelConfig, params, batch: dict):
    """batch: {tokens [B,S], labels [B,S], mask [B,S]} -> (loss, metrics)."""
    extra = batch.get("patches")
    logits, aux = lm_forward(
        cfg, params, batch["tokens"], extra_embed=extra,
        prefix_len=(extra.shape[1] if extra is not None else None),
    )
    if extra is not None:
        logits = logits[:, extra.shape[1]:]  # loss over text positions only
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = -(ll * mask).sum() / denom
    loss = ce + aux
    metrics = {"loss": loss, "ce": ce, "aux": aux,
               "tokens": mask.sum()}
    return loss, metrics


# --------------------------------------------------------------------- decode

def init_lm_cache(cfg: ModelConfig, batch: int, cache_len: int):
    one = lambda: init_block_cache(cfg, batch, cache_len)
    caches = jax.vmap(lambda _: one())(jnp.arange(cfg.n_layers))
    out = {"layers": caches}
    if cfg.family == "hybrid" and cfg.attn_every:
        full, _ = _hybrid_groups(cfg)
        hd = cfg.resolved_head_dim
        out["shared_attn"] = jax.vmap(
            lambda _: attn.init_decode_cache(cfg, batch, cache_len,
                                             cfg.n_kv_heads, hd)
        )(jnp.arange(full))
    return out


def _keep_layout(cache, layout):
    """``cache`` held in ``layout``; with ``None``, where the compiler puts it."""
    return cache if layout is None else with_layout_constraint(cache, layout)


def _decode_layers(cfg: ModelConfig, layers_p, x, cache, first, pos, layout):
    """Decode through stacked layers ``layers_p`` (``[n, ...]``), layer
    ``first`` onwards, carrying the whole stacked cache through the loop so
    each layer writes its entries into it in place. The experts' weights
    stay whole, outside the loop's slices: each layer's grouped product
    reads its own groups of the stack in place. Returns (x, cache)."""
    n = jax.tree.leaves(layers_p)[0].shape[0]
    whole = {}
    if "moe" in layers_p:
        moe = dict(layers_p["moe"])
        whole = {k: moe.pop(k) for k in moe_mod.EXPERTS}
        layers_p = dict(layers_p, moe=moe)

    def body(carry, inp):
        x, cache = carry
        lp, layer = inp
        if whole:
            lp = dict(lp, moe=dict(lp["moe"], **whole))
        x, cache = block_decode(cfg, lp, x, cache, layer, pos)
        return (x, _keep_layout(cache, layout)), None

    (x, cache), _ = maybe_scan(cfg, body, (x, cache),
                               (layers_p, first + jnp.arange(n)))
    return x, cache


def lm_decode_step(cfg: ModelConfig, params, cache: dict, tokens: jax.Array,
                   pos: jax.Array, cache_layout=None):
    """One decode step. tokens: [B,1]; pos: [] -> (logits [B,1,V], cache).

    Every family runs one loop: any leading dense layers, then groups of
    ``attn_every`` layers, each followed by the shared attention block
    (zamba2), then the remaining layers (all of them where no block is
    shared). The cache goes through
    the loops as their carry, never as scanned inputs and outputs, and
    each layer writes only its new entries.

    ``cache_layout`` (a ``Layout`` per leaf of ``cache``) holds the carried
    cache in that layout: given the layout of the step's cache argument,
    the step reads and writes the caller's (donated) buffer in place. Left
    free, a compiler may give the loop a layout of its own and copy the
    whole cache into and out of it on every step."""
    x = L.embed(cfg, params["embed"], tokens)
    if cfg.family == "vlm":
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    layout = cache_layout or {}
    full, tail = _hybrid_groups(cfg)
    k = cfg.attn_every
    layers_p, lc = params["layers"], cache["layers"]
    sac = cache.get("shared_attn")
    if cfg.first_k_dense:
        x, lc = _decode_layers(_leading(cfg), params["dense_layers"], x, lc,
                               0, pos, layout.get("layers"))
    if full:
        gp = jax.tree.map(lambda a: a[: full * k].reshape(full, k, *a.shape[1:]),
                          layers_p)

        def group_body(carry, inp):
            x, lc, sac = carry
            g_p, g = inp
            x, lc = _decode_layers(cfg, g_p, x, lc, g * k, pos,
                                   layout.get("layers"))
            x, sac = shared_attn_decode(cfg, params["shared_attn"], x, sac,
                                        g, pos)
            return (x, lc, _keep_layout(sac, layout.get("shared_attn"))), None

        (x, lc, sac), _ = maybe_scan(cfg, group_body, (x, lc, sac),
                                     (gp, jnp.arange(full)))
        layers_p = jax.tree.map(lambda a: a[full * k:], layers_p)
    if tail:
        x, lc = _decode_layers(cfg, layers_p, x, lc,
                               cfg.first_k_dense + full * k, pos,
                               layout.get("layers"))
    new_cache = {"layers": lc}
    if sac is not None:
        new_cache["shared_attn"] = sac

    x = L.norm(cfg, params["final_norm"], x)
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    logits = L.unembed(cfg, params.get("lm_head"), x, tied_table=tied)
    return logits, new_cache

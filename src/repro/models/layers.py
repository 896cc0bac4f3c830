"""Shared neural-net building blocks (pure functional, dict param trees).

Every module is an ``init_*(key, ...) -> params`` / ``*(params, x, ...) -> y``
pair. Params live in ``cfg.param_dtype``; compute casts to
``cfg.compute_dtype`` (bf16 by default) with f32 accumulation where it
matters (norms, softmax, losses).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding import shard_act


def dt(name: str):
    return jnp.dtype(name)


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: int):
    p = {"scale": jnp.ones((dim,), dt(cfg.param_dtype))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), dt(cfg.param_dtype))
    return p


def norm(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    # Keep the f32 widening sharded like the residual stream: without this,
    # GSPMD hoists the next matmul's all-gather ABOVE the bf16 downcast and
    # moves f32 activation bytes over ICI (§Perf it5 — measured 2× wire).
    xf = shard_act(xf, "batch", None, "model", kind="resid")
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"].astype(jnp.float32)
    y = y.astype(x.dtype)
    return shard_act(y, "batch", None, "model", kind="resid")


def rms_norm_headwise(x: jax.Array, scale: jax.Array) -> jax.Array:
    """qk-norm (qwen3): RMS-normalize the last (head) dim."""
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + 1e-6) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embed(cfg: ModelConfig, key, vocab: int, dim: int):
    # 0.02 std keeps tied-unembed logits sane at init (GPT/whisper convention)
    return {"table": _normal(key, (vocab, dim), 0.02, dt(cfg.param_dtype))}


def embed(cfg: ModelConfig, p, tokens: jax.Array) -> jax.Array:
    y = jnp.take(p["table"].astype(dt(cfg.compute_dtype)), tokens, axis=0)
    return shard_act(y, "batch", None, "model", kind="resid")


def unembed(cfg: ModelConfig, p, x: jax.Array, *, tied_table=None) -> jax.Array:
    """Project to vocab logits (f32)."""
    if tied_table is not None:
        w = tied_table.astype(dt(cfg.compute_dtype)).T  # [D, V]
    else:
        w = p["kernel"].astype(dt(cfg.compute_dtype))
    logits = jnp.einsum("...d,dv->...v", x, w).astype(jnp.float32)
    return shard_act(logits, "batch", None, "model")


def init_unembed(cfg: ModelConfig, key, dim: int, vocab: int):
    return {"kernel": _normal(key, (dim, vocab), dim ** -0.5, dt(cfg.param_dtype))}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # [Dh/2]


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: Optional[jax.Array] = None) -> jax.Array:
    """x: [..., S, H, Dh]; positions: [..., S] (broadcastable). Rotate-half
    form; ``freqs`` ([Dh/2]) replaces the plain frequencies of ``theta``."""
    dh = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dh, theta)  # [Dh/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, Dh/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., S, 1, Dh/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, dim: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    inv = 1.0 / (10_000.0 ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)  # [n, dim]


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU / GeGLU, or plain 2-layer)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, dim: int, hidden: int):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "w_up": _normal(k1, (dim, hidden), dim ** -0.5, dt(cfg.param_dtype)),
        "w_down": _normal(k2, (hidden, dim), hidden ** -0.5, dt(cfg.param_dtype)),
    }
    if cfg.gated_mlp:
        p["w_gate"] = _normal(k3, (dim, hidden), dim ** -0.5, dt(cfg.param_dtype))
    return p


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {name}")


def mlp(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    cd = dt(cfg.compute_dtype)
    x = x.astype(cd)
    if cfg.mlp_tp_overlap and cfg.gated_mlp:
        from repro import sharding as shd

        mesh = shd.current_mesh()
        if (mesh is not None and "model" in mesh.axis_names
                and x.shape[1] % mesh.shape["model"] == 0):
            from repro.core.collective_matmul import mlp_ring

            # Relic two-lane ring: fused AG(gate+up) + RS(down), seq-sharded
            # residual stream; every ppermute overlaps the previous chunk's
            # matmul (docs/schedulers.md).
            return mlp_ring(cfg.act, x, p["w_gate"].astype(cd),
                            p["w_up"].astype(cd), p["w_down"].astype(cd), mesh,
                            full_unroll=not cfg.scan_layers)
    x = shard_act(x, "batch", None, None, kind="blockin")
    up = x @ p["w_up"].astype(cd)
    if cfg.gated_mlp:
        gate = _act(cfg.act, x @ p["w_gate"].astype(cd))
        h = gate * up
    else:
        h = _act(cfg.act, up)
    h = shard_act(h, "batch", None, "model")
    if cfg.bf16_reduce:
        y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(cd),
                       preferred_element_type=cd).astype(cd)
    else:
        y = h @ p["w_down"].astype(cd)
    return shard_act(y, "batch", None, "model", kind="resid")


# ---------------------------------------------------------------------------
# Stacked decode caches: every layer's entry in one [L, ...] array per leaf
# ---------------------------------------------------------------------------

def layer_entry(stacked, layer):
    """Layer ``layer``'s entry of a stacked cache, read by dynamic index."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
        stacked)


def write_layer(stacked, new, layer, pos):
    """Write one layer's new cache entries into the stacked cache in place.

    A leaf of ``new`` with the shape of the layer's whole entry (a recurrent
    state) is written whole; a shorter one is a run along the time axis
    (axis 1, after batch: an attention row) and is written at time ``pos``.
    """
    def put(a, n):
        at = [layer] + [0] * n.ndim
        if n.shape != a.shape[1:]:
            at[2] = pos
        return jax.lax.dynamic_update_slice(a, n[None].astype(a.dtype), at)

    return jax.tree.map(put, stacked, new)

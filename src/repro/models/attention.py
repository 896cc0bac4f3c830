"""GQA attention: full, chunked (flash-style streaming softmax in XLA), and
cached decode paths, plus cross-attention for encoder-decoder models.

The chunked path is the *portable* flash attention: a `lax.scan` over KV
blocks carrying the running (max, denominator, accumulator) — bounded memory
in the HLO itself, so 32k-token prefill lowers without materializing S×S
scores. On TPU the Pallas kernel (`repro.kernels.flash_attention`) is the
fast path; `repro.kernels.ops` dispatches between them.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (_normal, apply_rope, dt, layer_entry,
                                 rms_norm_headwise, write_layer)
from repro.sharding import shard_act

NEG_INF = -1e30


def init_attention(
    cfg: ModelConfig,
    key,
    dim: int,
    n_heads: int,
    n_kv: int,
    head_dim: int,
):
    kq, kk, kv, ko, _ = jax.random.split(key, 5)
    pd = dt(cfg.param_dtype)
    scale = dim ** -0.5
    p = {
        "wq": _normal(kq, (dim, n_heads, head_dim), scale, pd),
        "wk": _normal(kk, (dim, n_kv, head_dim), scale, pd),
        "wv": _normal(kv, (dim, n_kv, head_dim), scale, pd),
        "wo": _normal(ko, (n_heads, head_dim, dim), (n_heads * head_dim) ** -0.5, pd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), pd)
        p["k_norm"] = jnp.ones((head_dim,), pd)
    return p


# ---------------------------------------------------------------------------
# Cores (operate on projected q/k/v)
# ---------------------------------------------------------------------------

def _grouped(q: jax.Array, n_kv: int):
    """[B,S,H,Dh] -> [B,S,Kv,G,Dh]"""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def attention_full(
    q: jax.Array,          # [B,Sq,H,Dh]
    k: jax.Array,          # [B,Sk,Kv,Dh]
    v: jax.Array,          # [B,Sk,Kv,Dh]
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    prefix_len: Optional[int] = None,
) -> jax.Array:
    """Unchunked reference path (scores materialized)."""
    n_kv = k.shape[2]
    qg = _grouped(q, n_kv)  # [B,Sq,Kv,G,Dh]
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    sq, sk = q.shape[1], k.shape[1]
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(sk)
        mask = qpos[:, None] >= kpos[None, :]
        if prefix_len is not None:  # prefix-LM: bidirectional over the prefix
            mask = mask | (kpos[None, :] < prefix_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(q.shape).astype(q.dtype)


def attention_chunked(
    q: jax.Array,          # [B,Sq,H,Dh]
    k: jax.Array,          # [B,Sk,Kv,Dh]
    v: jax.Array,          # [B,Sk,Kv,Dh]
    *,
    causal: bool,
    chunk_q: int = 512,
    chunk_k: int = 1024,
    q_offset: int = 0,
    prefix_len: Optional[int] = None,
    causal_skip: bool = False,
    full_unroll: bool = False,
) -> jax.Array:
    """Flash-style two-level streaming attention in pure XLA.

    Outer scan over Q blocks; inner scan over KV blocks carrying the running
    (m, l, acc). The inner carry is the SPSC handoff of the paper's pattern:
    block t's statistics are produced for block t+1's consumption — a static
    two-lane chain with no dynamic scheduling.

    causal_skip: per-Q-block inner scans only visit KV blocks at or below the
    diagonal — removes the ~2× masked-block waste of causal attention (§Perf).
    full_unroll: statically expand both scans so HloCostAnalysis counts every
    block (dry-run cost lowerings; a rolled loop body is counted once).
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    n_kv = k.shape[2]
    g = h // n_kv
    chunk_q = min(chunk_q, sq)
    chunk_k = min(chunk_k, sk)
    nq, nk = sq // chunk_q, sk // chunk_k
    assert sq % chunk_q == 0 and sk % chunk_k == 0, (sq, chunk_q, sk, chunk_k)
    scale = dh ** -0.5

    qg = _grouped(q, n_kv).reshape(b, nq, chunk_q, n_kv, g, dh)
    kb = k.reshape(b, nk, chunk_k, n_kv, dh)
    vb = v.reshape(b, nk, chunk_k, n_kv, dh)

    def q_block(qi, q_blk, nk_used):
        # q_blk: [B,Cq,Kv,G,Dh]; inner scan over the first nk_used kv blocks
        qf = q_blk.astype(jnp.float32) * scale
        m0 = jnp.full((b, n_kv, g, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, chunk_q), jnp.float32)
        a0 = jnp.zeros((b, chunk_q, n_kv, g, dh), jnp.float32)

        def kv_block(carry, inputs):
            m, l, acc = carry
            ki, k_blk, v_blk = inputs
            s = jnp.einsum("bqkgd,btkd->bkgqt", qf, k_blk.astype(jnp.float32))
            if causal:
                qpos = qi * chunk_q + jnp.arange(chunk_q) + q_offset
                kpos = ki * chunk_k + jnp.arange(chunk_k)
                mask = qpos[:, None] >= kpos[None, :]
                if prefix_len is not None:
                    mask = mask | (kpos[None, :] < prefix_len)
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
                "bkgqt,btkd->bqkgd", p, v_blk.astype(jnp.float32)
            )
            return (m_new, l, acc), None

        ks = jnp.arange(nk_used)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (ks, kb.swapaxes(0, 1)[:nk_used], vb.swapaxes(0, 1)[:nk_used]),
            unroll=nk_used if full_unroll else 1,
        )
        out = acc / l.transpose(0, 3, 1, 2)[..., None]
        return out.reshape(b, chunk_q, h, dh)

    skip = causal_skip and causal and prefix_len is None and q_offset == 0
    if skip:
        # Variable-length inner scans: q block qi only needs kv blocks
        # covering positions [0, (qi+1)*Cq) — exact causal FLOPs.
        outs = [
            q_block(qi, qg[:, qi], -(-((qi + 1) * chunk_q) // chunk_k))
            for qi in range(nq)
        ]
        out = jnp.concatenate(outs, axis=1)  # [B, Sq, H, Dh]
        return out.astype(q.dtype)

    def outer(_, args):
        qi, q_blk = args
        return None, q_block(qi, q_blk, nk)

    _, out = jax.lax.scan(
        outer, None, (jnp.arange(nq), qg.swapaxes(0, 1)),
        unroll=nq if full_unroll else 1,
    )
    # out: [nq, B, Cq, H, Dh] -> [B, Sq, H, Dh]
    out = out.swapaxes(0, 1).reshape(b, sq, h, dh)
    return out.astype(q.dtype)


def _terms(x: jax.Array, dtype) -> list[jax.Array]:
    """``x`` as terms of ``dtype`` that sum to it: ``x`` itself where it has
    that dtype, else its rounding and the rounding of the rest. For f32 in
    bf16 the two hold ``x`` to ~2^-18, so a matmul of bf16 operands keeps
    f32's products."""
    if x.dtype == jnp.dtype(dtype):
        return [x]
    hi = x.astype(dtype)
    return [hi, (x - hi.astype(x.dtype)).astype(dtype)]


def _dot_terms(spec: str, xs: list[jax.Array], cache: jax.Array) -> jax.Array:
    """``einsum(spec, sum(xs), cache)`` in f32: the terms go through one
    matmul, concatenated along the label ``n``, and are summed."""
    ins, out = spec.split("->")
    res = jnp.einsum(spec, jnp.concatenate(xs, ins.split(",")[0].index("n")),
                     cache,
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return sum(jnp.split(res, len(xs), out.index("n")))


def attention_decode(
    q: jax.Array,          # [B,1,H,Dh]
    k: jax.Array,          # [B,T,Kv*Dh]
    v: jax.Array,          # [B,T,Kv*Dh]
    kv_len: jax.Array,
) -> jax.Array:
    """One query row against a lane-dense K/V cache; keys at ``kv_len`` and
    after are masked. Returns [B,1,H,Dh].

    Both products contract over the merged head axis on the matrix unit,
    so the slab is read in its own layout (split to ``[B,T,Kv,Dh]``, a TPU
    would lay the whole slab out anew). The scores take K against the
    block-diagonal query (head k's query in rows k*Dh..(k+1)*Dh of its
    column); the output takes P against all of V and keeps each head's own
    block. The query has the cache's dtype and P enters as terms in it
    (``_terms``), so the products keep f32's precision, as in
    ``attention_full``."""
    b, _, h, dh = q.shape
    t, width = k.shape[1:]
    n_kv = width // dh
    eye = jnp.eye(n_kv, dtype=q.dtype)
    qbd = jnp.einsum("bkgd,kj->bjdkg", _grouped(q, n_kv)[:, 0],
                     eye).reshape(b, width, h)
    s = _dot_terms("bcn,btc->btn", [qbd], k) * dh ** -0.5   # [B,T,H]
    s = jnp.where((jnp.arange(t) < kv_len)[None, :, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=1)
    full = _dot_terms("btn,btc->bnc", _terms(p, v.dtype), v)  # [B,H,Kv*Dh]
    own = jnp.arange(width) // dh == jnp.arange(n_kv)[:, None]  # [Kv,Kv*Dh]
    o = (full.reshape(b, n_kv, h // n_kv, width) * own[:, None]).sum(1)
    o = o.reshape(b, h // n_kv, n_kv, dh).transpose(0, 2, 1, 3)
    return o.reshape(b, 1, h, dh).astype(q.dtype)


def _pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (chunked attention tiling)."""
    for c in range(min(target, n), 0, -1):
        if n % c == 0:
            return c
    return n


def attention_core(
    cfg: ModelConfig,
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_offset: jax.Array | int = 0,
    prefix_len: Optional[int] = None,
) -> jax.Array:
    """Dispatch: kernels (TPU) > chunked (long S) > full."""
    sq, sk = q.shape[1], k.shape[1]
    if cfg.use_kernels and sq > 1 and prefix_len is None:
        from repro.kernels import ops  # deferred: kernels are optional

        return ops.flash_attention(q, k, v, causal=causal)
    if sq > 1 and max(sq, sk) >= cfg.attn_chunk_threshold:
        return attention_chunked(
            q, k, v, causal=causal,
            chunk_q=_pick_chunk(sq, cfg.attn_chunk_q),
            chunk_k=_pick_chunk(sk, cfg.attn_chunk),
            q_offset=q_offset, prefix_len=prefix_len,
            causal_skip=cfg.causal_skip,
            full_unroll=not cfg.scan_layers,  # exact dry-run cost accounting
        )
    return attention_full(q, k, v, causal=causal, q_offset=q_offset,
                          prefix_len=prefix_len)


# ---------------------------------------------------------------------------
# Full layer-level wrappers (projections + rope + cache handling)
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, p, x: jax.Array, x_kv: Optional[jax.Array] = None):
    cd = dt(cfg.compute_dtype)
    x = shard_act(x.astype(cd), "batch", None, None, kind="blockin")
    src = x if x_kv is None else x_kv.astype(cd)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(cd))
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"])
        k = rms_norm_headwise(k, p["k_norm"])
    q = shard_act(q, "batch", None, "model", None)
    k = shard_act(k, "batch", None, None, None)
    v = shard_act(v, "batch", None, None, None)
    return q, k, v


def _output(cfg: ModelConfig, p, o: jax.Array) -> jax.Array:
    cd = dt(cfg.compute_dtype)
    pet = cd if cfg.bf16_reduce else None  # bf16 cross-shard partial sums
    y = jnp.einsum("bshk,hkd->bsd", o.astype(cd), p["wo"].astype(cd),
                   preferred_element_type=pet)
    return shard_act(y.astype(cd), "batch", None, "model", kind="resid")


def self_attention(
    cfg: ModelConfig,
    p,
    x: jax.Array,
    *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    prefix_len: Optional[int] = None,
) -> jax.Array:
    """Training / prefill self-attention over [B,S,D]."""
    q, k, v = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = attention_core(cfg, q, k, v, causal=causal, prefix_len=prefix_len)
    return _output(cfg, p, o)


def cross_attention(
    cfg: ModelConfig,
    p,
    x: jax.Array,
    enc: jax.Array,
) -> jax.Array:
    q, k, v = _project_qkv(cfg, p, x, x_kv=enc)
    o = attention_core(cfg, q, k, v, causal=False)
    return _output(cfg, p, o)


def decode_self_attention(
    cfg: ModelConfig,
    p,
    x: jax.Array,           # [B,1,D]
    cache: dict,            # {"k": [B,T,Kv*Dh], "v": [B,T,Kv*Dh]}
    pos: jax.Array,         # [] int32 current position
    layer: Optional[jax.Array] = None,
):
    """One-token decode against a fixed-length KV cache; returns (y, cache).

    K and V are stored lane-dense, heads merged into the minor axis
    (``init_decode_cache``): the new row is written flat and the slab is
    read over the merged axis (``attention_decode``). With ``layer``,
    ``cache`` holds every layer's K/V stacked ([L,B,T,Kv*Dh]): only row
    ``(layer, :, pos)`` is written, and the layer's slab is read in place,
    so a step that carries the stack through its layer loop never copies
    it."""
    q, k_new, v_new = _project_qkv(cfg, p, x)
    if cfg.use_rope:
        posb = jnp.broadcast_to(pos, (x.shape[0], 1))
        q = apply_rope(q, posb, cfg.rope_theta)
        k_new = apply_rope(k_new, posb, cfg.rope_theta)
    b, _, n_kv, dh = k_new.shape
    pos = pos.astype(jnp.int32)
    new = {"k": k_new.reshape(b, 1, n_kv * dh),
           "v": v_new.reshape(b, 1, n_kv * dh)}
    if layer is None:
        zero = jnp.int32(0)
        cache = jax.tree.map(
            lambda a, n: jax.lax.dynamic_update_slice(
                a, n.astype(a.dtype), (zero, pos, zero)), cache, new)
        k, v = cache["k"], cache["v"]
    else:
        cache = write_layer(cache, new, layer, pos)
        slab = layer_entry(cache, layer)
        k, v = slab["k"], slab["v"]
    o = attention_decode(q, k, v, pos + 1)
    y = _output(cfg, p, o)
    return y, cache


def decode_cross_attention(
    cfg: ModelConfig,
    p,
    x: jax.Array,           # [B,1,D]
    cache: dict,            # {"xk": [B,T,Kv,Dh], "xv": ...} precomputed from encoder
):
    cd = dt(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cd), p["wq"].astype(cd))
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"])
    o = attention_core(cfg, q, cache["xk"].astype(cd), cache["xv"].astype(cd),
                       causal=False)
    return _output(cfg, p, o)


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int, n_kv: int,
                      head_dim: int):
    """A self-attention K/V cache, ``[B,T,Kv*Dh]`` per leaf. The heads share
    the minor axis so a device's default layout keeps it there, lane-dense,
    with time on the second-minor axis: a position's row write touches one
    tile row per batch row. Split (``[B,T,Kv,Dh]``), a TPU lays a head
    width that is no multiple of 128 out with time minor, and a row write
    rewrites the whole slab."""
    shape = (batch, max_len, n_kv * head_dim)
    dtype = dt(cfg.compute_dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

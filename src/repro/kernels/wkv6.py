"""RWKV-6 WKV chunked recurrence as a Pallas TPU kernel.

Layout: [B, H, T, K]. Grid: (batch, head, chunk) — the chunk axis is
sequential; the [K, K] state matrix lives in VMEM scratch and is handed from
chunk t to chunk t+1 (the SPSC chunk-state chain pattern, here with
zero HBM round-trips for the state). Within a chunk the recurrence is the
matmul-form expansion (cumulative log-decay rescaling), so the MXU does the
work while the next chunk's r/k/v/w blocks stream in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(chunk, r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0, 0].astype(jnp.float32)      # [C, K]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)         # [1, K]

    c = r.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # inclusive cumulative log decay as a lower-triangular matmul (Mosaic
    # has no cumsum lowering)
    la = jnp.dot((row >= col).astype(jnp.float32), lw,
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
    la_prev = la - lw
    r_dec = r * jnp.exp(la_prev)

    strict = (row > col).astype(jnp.float32)
    # pairwise per-channel decays, clamped at 0 — exact for the kept strict-
    # causal pairs (their exponent is <= 0), NaN-proof for the masked ones.
    diff = jnp.minimum(la_prev[:, None, :] - la[None, :, :], 0.0)  # [C,C,K]
    scores = (r[:, None, :] * k[None, :, :] * jnp.exp(diff)).sum(-1) * strict
    diag = (r * u * k).sum(-1)               # bonus term at tau == t
    scores = scores + jnp.where(row == col, diag[:, None], 0.0)

    out = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    out = out + jnp.dot(r_dec, state_ref[...], preferred_element_type=jnp.float32)
    o_ref[0, 0] = out.astype(o_ref.dtype)

    total = la[c - 1:c]                      # [1, K]
    k_fut = k * jnp.exp(total - la)
    state_ref[...] = state_ref[...] * jnp.exp(total).T + jnp.dot(
        k_fut.T, v, preferred_element_type=jnp.float32)


def wkv6_bhtk(
    r: jax.Array,      # [B, H, T, K]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,   # [B, H, T, K] log decay (<= 0)
    u: jax.Array,      # [H, K], passed as [H, 1, K] so its block tiles
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    b, h, t, kk = r.shape
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    kernel = functools.partial(_wkv_kernel, chunk)
    spec = pl.BlockSpec((1, 1, chunk, kk), lambda bi, hi, ci: (bi, hi, ci, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, t // chunk),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, 1, kk), lambda bi, hi, ci: (hi, 0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, t, kk), r.dtype),
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u[:, None, :])

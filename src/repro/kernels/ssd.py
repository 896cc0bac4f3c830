"""Mamba-2 SSD chunked recurrence as a Pallas TPU kernel.

Layout: x [B, H, T, P], a (log decay) [B, T, H], b/c [B, T, N] (shared across
heads, n_groups=1). ``a`` keeps the model's [B, T, H] layout so its block's
last two dims tile on the TPU; the kernel selects its head's column.
Grid: (batch, head, chunk), sequential chunk axis with the [P, N] state in
VMEM scratch — same SPSC chunk-state chain as wkv6 but with scalar-per-step
decay, so the intra-chunk term is a clean C×C matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(chunk, x_ref, a_ref, b_ref, c_ref, o_ref, state_ref):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)      # [C, P]
    a_all = a_ref[0].astype(jnp.float32)     # [C, H]
    b = b_ref[0].astype(jnp.float32)         # [C, N]
    c = c_ref[0].astype(jnp.float32)         # [C, N]

    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # this head's decay column, selected by a one-hot matmul (exact at
    # HIGHEST precision; a dynamic lane slice does not lower)
    pick = (jax.lax.broadcasted_iota(jnp.int32, (a_all.shape[1], 1), 0)
            == hi).astype(jnp.float32)
    a = jnp.dot(a_all, pick, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)            # [C, 1]
    # inclusive cumulative log decay as a lower-triangular matmul (Mosaic
    # has no cumsum lowering)
    la = jnp.dot((row >= col).astype(jnp.float32), a,
                 preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)          # [C, 1]
    cb = jnp.dot(c, b.T, preferred_element_type=jnp.float32)    # [C, C]
    la_rows = jnp.broadcast_to(la, (n, n))   # [t, s] -> la_t
    decay = jnp.exp(la_rows - la_rows.T)
    w = jnp.where(row >= col, cb * decay, 0.0)
    y = jnp.dot(w, x, preferred_element_type=jnp.float32)       # [C, P]

    # inter-chunk: y_t += exp(la_t) * c_t . state   (state: [P, N])
    y = y + jnp.exp(la) * jnp.dot(
        c, state_ref[...].T, preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)

    la_end = la[n - 1:n]                     # [1, 1]
    dec_end = jnp.exp(la_end - la)           # [C, 1]
    # la_end as a [P, 1] column: Mosaic cannot broadcast [1, 1] over both
    # sublanes and lanes in one step
    la_end_col = jnp.broadcast_to(la.T, (x.shape[1], n))[:, n - 1:n]
    state_ref[...] = state_ref[...] * jnp.exp(la_end_col) + jnp.dot(
        (x * dec_end).T, b, preferred_element_type=jnp.float32)


def ssd_bhtp(
    x: jax.Array,      # [B, H, T, P]
    a: jax.Array,      # [B, T, H]
    b: jax.Array,      # [B, T, N]
    c: jax.Array,      # [B, T, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bb, h, t, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    kernel = functools.partial(_ssd_kernel, chunk)
    return pl.pallas_call(
        kernel,
        grid=(bb, h, t // chunk),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, chunk, h), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bb, h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, a, b, c)

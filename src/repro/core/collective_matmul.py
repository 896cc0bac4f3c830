"""Overlapped collective matmuls — the Relic SPSC ring on the ICI fabric.

Megatron-style tensor parallelism needs two collectives per block:

  * ``f``: all-gather sequence-sharded activations before a column-parallel
    matmul;
  * ``g``: reduce-scatter the row-parallel matmul's partial sums back to
    sequence shards.

The unoverlapped forms serialize ICI transfer and MXU compute. Following the
paper's producer/consumer specialization, we replace each with a **static
ring**: at every step one ``ppermute`` (transfer lane) moves the next chunk
while the MXU (compute lane) consumes the current one — a depth-1 SPSC queue
between two fixed-role lanes, no dynamic scheduling. This is the established
"collective matmul" decomposition (Wang et al., ASPLOS'23), which we adopt
here explicitly as the TPU translation of Relic's SPSC pipeline.

All functions below run **inside shard_map** (per-device views). Reference
(unoverlapped) implementations live alongside for A/B in §Perf and for the
numerical tests.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.lanes import two_lane_ring


def _varying(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark a replicated value as device-varying along ``axis_name``.

    shard_map's vma type system requires loop carries that *become* varying
    (our ring buffers do, after the first ppermute) to start varying."""
    return lax.pcast(x, (axis_name,), to="varying")


# --------------------------------------------------------------------------
# Reference (unoverlapped) forms
# --------------------------------------------------------------------------

def allgather_matmul_ref(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """y = allgather(x, seq axis) @ w   (x: [S/p, K], w: [K, N/p] local)."""
    x_full = lax.all_gather(x, axis_name, axis=0, tiled=True)  # [S, K]
    return x_full @ w  # [S, N/p]


def matmul_reducescatter_ref(y: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """z = reduce_scatter(y @ w, seq axis)  (y: [S, N/p], w: [N/p, K] local)."""
    partial_z = y @ w  # [S, K] partial sum over the sharded N dimension
    return lax.psum_scatter(partial_z, axis_name, scatter_dimension=0, tiled=True)


# --------------------------------------------------------------------------
# Overlapped ring forms (two-lane)
# --------------------------------------------------------------------------

def allgather_matmul(
    x: jax.Array,
    w: jax.Array,
    axis_name: str,
    *,
    unroll: int = 1,
) -> jax.Array:
    """Ring all-gather-matmul: y[S, N/p] from x[S/p, K] and w[K, N/p].

    Step ``s``: device ``d`` holds the x-chunk originally from device
    ``(d + s) % p``; it computes that chunk's rows of y while ppermuting the
    chunk to neighbor ``d - 1`` (so everyone eventually sees every chunk).
    The ppermute for step ``s+1`` is issued before step ``s``'s matmul —
    transfer lane producing, compute lane consuming.
    """
    p = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    s_loc, k = x.shape
    n = w.shape[1]
    perm = [(i, (i - 1) % p) for i in range(p)]

    def transfer(step, buf):
        del step
        return lax.ppermute(buf, axis_name, perm)

    def compute(step, buf, acc):
        # buf holds the chunk of device (d + step) % p.
        src = (d + step) % p
        acc = lax.dynamic_update_slice(acc, buf @ w, (src * s_loc, jnp.int32(0)))
        return acc

    acc0 = _varying(
        jnp.zeros((p * s_loc, n), dtype=jnp.promote_types(x.dtype, w.dtype)),
        axis_name,
    )
    acc = two_lane_ring(p, x, acc0, compute, transfer, unroll=unroll)
    return acc.astype(jnp.promote_types(x.dtype, w.dtype))


def matmul_reducescatter(
    y: jax.Array,
    w: jax.Array,
    axis_name: str,
    *,
    unroll: int = 1,
) -> jax.Array:
    """Ring matmul-reduce-scatter: z[S/p, K] from y[S, N/p] and w[N/p, K].

    The partial product for one sequence chunk is computed per step and added
    to the accumulator ring-permuting toward its home device: compute lane
    produces chunk partials, transfer lane (ppermute) is the consumer carrying
    the running sum — the same SPSC ring with the roles mirrored.
    """
    p = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    s = y.shape[0]
    s_loc = s // p
    k = w.shape[1]
    perm = [(i, (i + 1) % p) for i in range(p)]

    # Chunk schedule: the in-flight buffer that will finally land on device
    # ``h`` sits on device ``(h + t) % p`` at step ``t``; a device holding it
    # must therefore contribute its partial for chunk ``(d - t) % p``. After
    # the add, the buffer permutes one hop toward home. The buffer *is* the
    # SPSC slot; compute lane produces partials, transfer lane consumes them.
    buf0 = _varying(jnp.zeros((s_loc, k), dtype=jnp.float32), axis_name)  # f32 ring acc

    def body(step, buf):
        c = (d - step) % p
        y_chunk = lax.dynamic_slice(y, (c * s_loc, jnp.int32(0)), (s_loc, y.shape[1]))
        buf = buf + (y_chunk @ w).astype(buf.dtype)
        buf = lax.ppermute(buf, axis_name, perm)
        return buf

    buf = lax.fori_loop(0, p, body, buf0, unroll=unroll)
    return buf.astype(jnp.promote_types(y.dtype, w.dtype))


def allgather_matmul_gated(
    x: jax.Array,       # [S/p, K]   sequence-sharded activations (local)
    w_gate: jax.Array,  # [K, N/p]   column-sharded (local)
    w_up: jax.Array,    # [K, N/p]
    axis_name: str,
    *,
    act: str = "silu",
    unroll: int = 1,
) -> jax.Array:
    """Fused two-lane ring: one x-chunk transfer feeds BOTH gate and up
    matmuls (halves ring traffic vs two separate AG-matmuls); elementwise
    act(g)*u happens on the consumer lane. Output: [S, N/p]."""
    p = lax.axis_size(axis_name)
    d = lax.axis_index(axis_name)
    s_loc, k = x.shape
    n = w_gate.shape[1]
    perm = [(i, (i - 1) % p) for i in range(p)]

    def transfer(step, buf):
        del step
        return lax.ppermute(buf, axis_name, perm)

    def compute(step, buf, acc):
        src = (d + step) % p
        g = buf @ w_gate
        u = buf @ w_up
        if act == "silu":
            g = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)
        elif act == "gelu":
            g = jax.nn.gelu(g.astype(jnp.float32)).astype(g.dtype)
        h = g * u
        return lax.dynamic_update_slice(acc, h, (src * s_loc, jnp.int32(0)))

    acc0 = _varying(
        jnp.zeros((p * s_loc, n), dtype=jnp.promote_types(x.dtype, w_gate.dtype)),
        axis_name,
    )
    return two_lane_ring(p, x, acc0, compute, transfer, unroll=unroll)


def mlp_ring(cfg_act: str, x: jax.Array, w_gate, w_up, w_down,
             mesh, axis_name: str = "model", *, full_unroll: bool = False):
    """Relic-ring TP MLP over a sequence-sharded residual stream.

    x: [B, S(model-sharded), D]; weights Megatron column/row sharded on the
    model axis. One AG ring (fused gate+up) + one RS ring; every transfer
    overlaps the previous chunk's MXU work. Returns [B, S(model-sharded), D].

    full_unroll statically expands the ring (dry-run cost lowerings: XLA's
    HloCostAnalysis counts a rolled loop body once).
    """
    P = jax.sharding.PartitionSpec
    unroll = mesh.shape[axis_name] if full_unroll else 1

    def local(xl, wg, wu, wd):
        b, s_loc, k = xl.shape
        x2 = xl.reshape(b * s_loc, k)
        h = allgather_matmul_gated(x2, wg, wu, axis_name, act=cfg_act,
                                   unroll=unroll)
        out = matmul_reducescatter(h, wd, axis_name, unroll=unroll)
        return out.reshape(b, s_loc, wd.shape[1]).astype(xl.dtype)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis_name, None), P(None, axis_name),
                  P(None, axis_name), P(axis_name, None)),
        out_specs=P(None, axis_name, None),
        axis_names={axis_name},
    )(x, w_gate, w_up, w_down)


# --------------------------------------------------------------------------
# shard_map front-ends (mesh-level API used by the model code)
# --------------------------------------------------------------------------

def tp_allgather_matmul(
    x_sharded: jax.Array,
    w_col: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "model",
    *,
    overlapped: bool = True,
):
    """Mesh-level f-layer: x [.., S(model-sharded), K] @ w [K, N(model-sharded)]."""
    P = jax.sharding.PartitionSpec
    fn = allgather_matmul if overlapped else allgather_matmul_ref

    def local(x, w):
        return fn(x, w, axis_name)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(None, axis_name)),
        out_specs=P(None, axis_name),
    )(x_sharded, w_col)


def tp_matmul_reducescatter(
    y: jax.Array,
    w_row: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "model",
    *,
    overlapped: bool = True,
):
    """Mesh-level g-layer: y [S, N(model-sharded)] @ w [N(model-sharded), K]."""
    P = jax.sharding.PartitionSpec
    fn = matmul_reducescatter if overlapped else matmul_reducescatter_ref

    def local(y, w):
        return fn(y, w, axis_name)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None),
    )(y, w_row)

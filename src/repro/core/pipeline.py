"""Inter-pod pipeline parallelism (GPipe schedule) — the third scale of the
Relic pattern.

Pods are connected by slower DCN/ICI links than chips within a pod, so the
natural pod-axis parallelism choices are pure DP (the dry-run default) or
**pipeline stages**. This module implements the latter: contiguous layer
blocks live on each pod (`stage = pod index`), microbatches stream through,
and the stage→stage activation handoff is a `ppermute` — a fixed-role
producer/consumer chain with a depth-1 buffer, i.e. the paper's SPSC queue
stretched across pods.

Schedule: GPipe (fill, steady state, drain): T = M + S - 1 ticks for M
microbatches over S stages. Bubble fraction = (S-1)/(M+S-1); callers pick
M >> S. Reverse-mode AD works through the schedule (static trip counts), so
`jax.grad` of a pipelined loss gives pipelined backward for free — XLA
schedules the backward ppermutes against the backward stage compute.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x_mb: jax.Array,
    mesh,
    *,
    axis_name: str = "pod",
) -> jax.Array:
    """Run microbatches through pod-resident pipeline stages.

    Args:
      stage_fn: ``(stage_params_local, x) -> y`` — one stage's layer block
        applied to one microbatch activation ``[mb, S, D]``.
      stage_params: pytree with leading dim = n_stages, sharded over
        ``axis_name`` (each pod holds exactly its stage's slice).
      x_mb: ``[M, mb, S, D]`` microbatches (replicated across the axis).
      mesh: the device mesh containing ``axis_name``.

    Returns: ``[M, mb, S, D]`` outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[axis_name]
    m = x_mb.shape[0]
    ticks = m + n_stages - 1
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def local(params_local, x_all):
        # params_local: [1, ...] this pod's stage block; x_all: [M, mb, S, D]
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = lax.axis_index(axis_name)
        mb_shape = x_all.shape[1:]

        def tick(t, carry):
            in_buf, outputs = carry
            mb_idx = t - stage                      # microbatch at this stage
            active = (mb_idx >= 0) & (mb_idx < m)
            safe_idx = jnp.clip(mb_idx, 0, m - 1)
            # stage 0 consumes fresh microbatches; others consume the buffer
            # filled by their upstream neighbor last tick (the SPSC slot).
            x_in = jnp.where(stage == 0,
                             lax.dynamic_index_in_dim(x_all, safe_idx, 0,
                                                      keepdims=False),
                             in_buf)
            y = stage_fn(params_me, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # producer lane: hand the activation to the next stage
            out_buf = lax.ppermute(y, axis_name, fwd_perm)
            # last stage retires finished microbatches
            is_last = stage == n_stages - 1
            write_idx = jnp.where(active & is_last, safe_idx, m)  # m == drop
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(active & is_last, y,
                          lax.dynamic_index_in_dim(outputs,
                                                   jnp.minimum(write_idx, m - 1),
                                                   0, keepdims=False)),
                jnp.minimum(write_idx, m - 1), 0)
            return out_buf, outputs

        buf0 = lax.pcast(jnp.zeros(mb_shape, x_all.dtype), (axis_name,),
                         to="varying")
        outputs0 = lax.pcast(jnp.zeros((m,) + mb_shape, x_all.dtype),
                             (axis_name,), to="varying")
        _, outputs = lax.fori_loop(0, ticks, tick, (buf0, outputs0))
        # only the last stage holds real outputs; broadcast them to every pod
        # (psum of one-hot contributions — replicated result).
        is_last = (lax.axis_index(axis_name) == n_stages - 1)
        contrib = jnp.where(is_last, outputs, jnp.zeros_like(outputs))
        return lax.psum(contrib, axis_name)

    n_leading = {a.shape[0] for a in jax.tree.leaves(stage_params)}
    assert n_leading == {n_stages}, (n_leading, n_stages)
    in_specs = (jax.tree.map(lambda _: P(axis_name), stage_params), P())
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names={axis_name},
    )(stage_params, x_mb)


def split_stages(layers_stacked: Any, n_stages: int) -> Any:
    """[L, ...] stacked layer params -> [n_stages, L/n_stages, ...]."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape(n_stages, l // n_stages, *a.shape[1:])

    return jax.tree.map(one, layers_stacked)

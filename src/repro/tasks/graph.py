"""The paper's fine-grained graph kernels (§IV-A), in JAX.

GAP-style kernels on the paper's input: a generated Kronecker graph with 32
nodes and ~157 undirected edges (degree 4 => scale 5, edgefactor ~4.9). At
n=32 a dense adjacency matrix is the right representation on vector units —
every kernel becomes a handful of matvecs/matmuls, which is both the fastest
JAX realization and microsecond-granularity work, matching the paper's
0.4–6.4 µs task sizes.

CC uses the label-propagation fixpoint (the linear-algebra twin of
Shiloach-Vishkin's hook+compress, chosen by the paper for fine-grained
inputs); SSSP is dense Bellman-Ford (min-plus matvec) rather than
delta-stepping — equivalent output, vector-friendly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.metrics import span

INF = jnp.float32(1e9)
# Matvecs over non-0/1 values (rank, path counts, dependencies) run at f32
# precision: the TPU's default rounds f32 matmul operands to bf16, beyond
# what the oracles allow. On the CPU it changes nothing.
_F32 = jax.lax.Precision.HIGHEST


def kronecker_graph(scale: int = 5, edge_factor: int = 16, seed: int = 10):
    # defaults reproduce the paper's input: 32 nodes, 157 undirected edges
    """Graph500-style Kronecker generator (A,B,C = .57,.19,.19), dedup'd,
    no self-loops. Returns (dense adjacency f32 [n,n], edge weights [n,n])."""
    n = 2 ** scale
    m = edge_factor * n
    rng = np.random.default_rng(seed)
    a, b, c = 0.57, 0.19, 0.19
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > a + b
        dst_bit = (r1 > a + b) & (r2 > c / (c + 0.05)) | \
                  (r1 <= a + b) & (r2 > a / (a + b))
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    mask = src != dst
    src, dst = src[mask], dst[mask]
    adj = np.zeros((n, n), np.float32)
    adj[src, dst] = 1.0
    adj[dst, src] = 1.0
    wrng = np.random.default_rng(seed + 1)
    w = wrng.integers(1, 8, size=(n, n)).astype(np.float32)
    w = np.where(adj > 0, np.maximum(w, w.T), np.float32(1e9))
    np.fill_diagonal(w, 0.0)
    return jnp.asarray(adj), jnp.asarray(w)


def n_edges(adj: jax.Array) -> int:
    return int(np.asarray(adj).sum() / 2)


# ---------------------------------------------------------------------------
# Kernels. Each is (adj[, w], args) -> array, designed to jit cleanly.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_iter",))
def bfs(adj: jax.Array, source: int = 0, max_iter: int = 32) -> jax.Array:
    """Level array (distance in hops; -1 unreachable)."""
    n = adj.shape[0]
    dist = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((n,), jnp.float32).at[source].set(1.0)

    def body(carry):
        dist, frontier, level = carry
        nxt = (adj.T @ frontier > 0) & (dist < 0)
        dist = jnp.where(nxt, level + 1, dist)
        return dist, nxt.astype(jnp.float32), level + 1

    def cond(carry):
        _, frontier, level = carry
        return (frontier.sum() > 0) & (level < max_iter)

    dist, _, _ = jax.lax.while_loop(cond, body, (dist, frontier, jnp.int32(0)))
    return dist


@jax.jit
def connected_components(adj: jax.Array) -> jax.Array:
    """Min-label propagation to fixpoint (Shiloach-Vishkin-style)."""
    n = adj.shape[0]
    big = jnp.float32(n + 1)
    labels = jnp.arange(n, dtype=jnp.float32)
    conn = adj + jnp.eye(n)

    def body(carry):
        labels, _ = carry
        # min over neighbors (masked min-plus with 0/1 adjacency)
        cand = jnp.min(jnp.where(conn > 0, labels[None, :], big), axis=1)
        changed = jnp.any(cand < labels)
        return jnp.minimum(labels, cand), changed

    labels, _ = jax.lax.while_loop(lambda c: c[1], body,
                                   (labels, jnp.bool_(True)))
    return labels.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("iters",))
def pagerank(adj: jax.Array, iters: int = 20, d: float = 0.85) -> jax.Array:
    n = adj.shape[0]
    deg = jnp.maximum(adj.sum(axis=1), 1.0)
    p = jnp.full((n,), 1.0 / n, jnp.float32)

    def body(_, p):
        spread = jnp.matmul(adj.T, p / deg, precision=_F32)
        return (1 - d) / n + d * spread

    return jax.lax.fori_loop(0, iters, body, p)


@functools.partial(jax.jit, static_argnames=("max_iter",))
def sssp(w: jax.Array, source: int = 0, max_iter: int = 32) -> jax.Array:
    """Bellman-Ford min-plus relaxation to fixpoint."""
    n = w.shape[0]
    dist = jnp.full((n,), INF).at[source].set(0.0)

    def body(carry):
        dist, _, it = carry
        cand = jnp.min(dist[:, None] + w, axis=0)
        new = jnp.minimum(dist, cand)
        return new, jnp.any(new < dist) & (it < max_iter), it + 1

    dist, _, _ = jax.lax.while_loop(
        lambda c: c[1], body, (dist, jnp.bool_(True), jnp.int32(0)))
    return dist


@jax.jit
def triangle_count(adj: jax.Array) -> jax.Array:
    """#triangles = trace(A^3) / 6 — computed as sum(A * A@A) / 6."""
    return jnp.sum(adj * (adj @ adj)) / 6.0


@functools.partial(jax.jit, static_argnames=("source", "max_iter"))
def betweenness_centrality(adj: jax.Array, source: int = 0,
                           max_iter: int = 32) -> jax.Array:
    """Single-source Brandes: forward BFS with path counts, backward
    dependency accumulation (dense matvecs per level)."""
    n = adj.shape[0]
    dist = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    sigma = jnp.zeros((n,), jnp.float32).at[source].set(1.0)

    def fwd(carry):
        dist, sigma, frontier, level = carry
        contrib = jnp.matmul(adj.T, sigma * frontier, precision=_F32)
        nxt = (adj.T @ frontier.astype(jnp.float32) > 0) & (dist < 0)
        sigma = jnp.where(nxt, contrib, sigma)
        dist = jnp.where(nxt, level + 1, dist)
        return dist, sigma, nxt.astype(jnp.float32), level + 1

    def fwd_cond(carry):
        _, _, frontier, level = carry
        return (frontier.sum() > 0) & (level < max_iter)

    frontier0 = jnp.zeros((n,), jnp.float32).at[source].set(1.0)
    dist, sigma, _, max_level = jax.lax.while_loop(
        fwd_cond, fwd, (dist, sigma, frontier0, jnp.int32(0)))

    delta = jnp.zeros((n,), jnp.float32)

    def bwd(i, delta):
        level = max_level - i  # descend levels
        on_next = (dist == level).astype(jnp.float32)
        coeff = jnp.where(sigma > 0, (1.0 + delta) / jnp.maximum(sigma, 1e-9),
                          0.0) * on_next
        contrib = jnp.matmul(adj, coeff, precision=_F32) * sigma
        on_this = (dist == level - 1).astype(jnp.float32)
        return delta + contrib * on_this

    delta = jax.lax.fori_loop(0, max_level, bwd, delta)
    return delta.at[source].set(0.0)


# ---------------------------------------------------------------------------
# Dependency-aware wavefront execution: GAP kernels over the tasking façade.
# ---------------------------------------------------------------------------

def run_wavefronts(tasks, scheduler):
    """Legacy dict-of-tuples front door for wavefront execution.

    ``tasks`` maps name -> ``(fn, deps)``; ``fn`` receives its
    dependencies' results positionally (in ``deps`` order). This shim
    validates the dict (``ValueError`` on unknown dependencies or cycles,
    as always), topo-sorts it into a :class:`repro.tasks.api.TaskGraph`,
    and executes it over ``scheduler`` through a borrowed
    :class:`repro.tasks.api.TaskScope` — new code should build the
    ``TaskGraph`` directly (see ``gap_task_graph``). The scheduler must
    already be started; it is left running (callers own its lifecycle).
    Returns ``{name: result}``.
    """
    from repro.tasks.api import TaskGraph, TaskScope

    for name, (_, deps) in tasks.items():
        for d in deps:
            if d not in tasks:
                raise ValueError(f"task {name!r} depends on unknown {d!r}")

    g = TaskGraph()
    pending = dict(tasks)
    while pending:
        ready = [n for n, (_, deps) in pending.items()
                 if all(d in g for d in deps)]
        if not ready:
            raise ValueError(f"dependency cycle among {sorted(pending)}")
        for n in ready:
            fn, deps = pending.pop(n)
            g.task(n, fn, deps=tuple(deps))

    from repro.core.schedulers import SchedulerUsageError
    if not getattr(scheduler, "_started", True):
        # Wrapping in a TaskScope would silently adopt (then close) an
        # unstarted scheduler; the documented contract is loud instead.
        raise SchedulerUsageError(
            "run_wavefronts() requires a started scheduler "
            "(callers own its lifecycle)")
    scope = TaskScope(scheduler)  # started instance => borrowed, not closed
    try:
        return g.run(scope)
    finally:
        scope.close()


def gap_task_graph(adj: jax.Array, w: jax.Array, source: int = 0):
    """The paper's GAP kernel suite as a :class:`repro.tasks.api.TaskGraph`.

    Wave 1 runs the five independent kernels; wave 2 runs betweenness
    centrality (reusing nothing device-side, but gated on ``bfs`` so the
    graph actually exercises dependencies) and a ``summary`` reduction over
    every kernel's output. Each task blocks on its device result so the
    scheduler measures real completion, not async dispatch. Run it with
    ``gap_task_graph(adj, w).run(scope_or_substrate)``.

    Spans: ``graph.dispatch`` around each jitted call, ``graph.sync``
    around its ``block_until_ready`` and around the summary's host reads.
    """
    from repro.tasks.api import TaskGraph

    def done(name, kernel, *args):
        with span("graph.dispatch", name=name):
            out = kernel(*args)
        with span("graph.sync", name=name):
            return jax.block_until_ready(out)

    def summary(b, c, pr, d, t, bc_):
        with span("graph.sync", name="summary"):
            return {
                "reached": int((np.asarray(b) >= 0).sum()),
                "components": int(len(np.unique(np.asarray(c)))),
                "pr_mass": float(np.asarray(pr).sum()),
                "finite_paths": int((np.asarray(d) < 1e8).sum()),
                "triangles": float(t),
                "max_bc": float(np.asarray(bc_).max()),
            }

    g = TaskGraph()
    g.task("bfs", lambda: done("bfs", bfs, adj, source))
    g.task("cc", lambda: done("cc", connected_components, adj))
    g.task("pagerank", lambda: done("pagerank", pagerank, adj))
    g.task("sssp", lambda: done("sssp", sssp, w, source))
    g.task("tc", lambda: done("tc", triangle_count, adj))
    g.task("bc", lambda _bfs: done("bc", betweenness_centrality, adj, source),
           deps=("bfs",))
    g.task("summary", summary,
           deps=("bfs", "cc", "pagerank", "sssp", "tc", "bc"))
    return g

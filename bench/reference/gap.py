"""Graph500 Kronecker input and plain references of the GAP kernels.

``kronecker_graph`` is a copy of ``repro.tasks.graph.kronecker_graph``
with the seed, scale, edge factor and initiator as arguments, so that the
input is made by the benchmark. The kernels follow the GAP benchmark
suite's definitions (Beamer et al., arXiv:1508.03619) as the configuration
states them, written with scalar loops over an edge list in float64 and
numpy: nothing of the program is imported.

``lowp_matvec`` is the control's arithmetic: a float32 product at the
TPU's ``Precision.HIGH`` (three bfloat16 passes) or ``DEFAULT`` (one pass),
done on the host so that it reads the same everywhere.
"""

from __future__ import annotations

from collections import deque

import ml_dtypes
import numpy as np

UNREACHED = 1e9   # the configuration's distance of an unreachable node


def kronecker_graph(scale: int, edge_factor: int, abc, seed: int,
                    max_weight: int):
    """Dense symmetric adjacency (float32 0/1, no self loops) and integer
    edge weights in [1, max_weight) (UNREACHED off the edges, 0 on the
    diagonal)."""
    n = 2 ** scale
    m = edge_factor * n
    rng = np.random.default_rng([seed, 0])
    a, b, c = abc
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
    keep = src != dst
    src, dst = src[keep], dst[keep]
    adj = np.zeros((n, n), np.float32)
    adj[src, dst] = 1.0
    adj[dst, src] = 1.0
    w = np.random.default_rng([seed, 1]).integers(
        1, max_weight, size=(n, n)).astype(np.float32)
    w = np.where(adj > 0, np.maximum(w, w.T), np.float32(UNREACHED))
    np.fill_diagonal(w, 0.0)
    return adj, w


def _neighbours(adj):
    return [np.flatnonzero(row) for row in np.asarray(adj) > 0]


def bfs(adj, source: int) -> np.ndarray:
    nb = _neighbours(adj)
    level = np.full(len(nb), -1, np.int64)
    level[source] = 0
    queue = deque([source])
    while queue:
        u = queue.popleft()
        for v in nb[u]:
            if level[v] < 0:
                level[v] = level[u] + 1
                queue.append(v)
    return level


def components(adj) -> np.ndarray:
    """Each node labelled with the smallest node id of its component."""
    nb = _neighbours(adj)
    label = np.full(len(nb), -1, np.int64)
    for s in range(len(nb)):
        if label[s] < 0:
            label[s] = s
            stack = [s]
            while stack:
                u = stack.pop()
                for v in nb[u]:
                    if label[v] < 0:
                        label[v] = s
                        stack.append(v)
    return label


def pagerank(adj, iters: int, d: float, matvec=None) -> np.ndarray:
    """``iters`` rounds of p <- (1-d)/n + d * A^T (p / deg), deg clamped to
    at least 1 (rank of a node with no edges is not passed on)."""
    a = np.asarray(adj, np.float64)
    n = len(a)
    deg = np.maximum(a.sum(1), 1.0)
    p = np.full(n, 1.0 / n)
    for _ in range(iters):
        x = p / deg
        spread = a.T @ x if matvec is None else matvec(a.T, x)
        p = (1 - d) / n + d * spread
    return p


def sssp(w, source: int) -> np.ndarray:
    """Dijkstra over the edges of weight below UNREACHED."""
    w = np.asarray(w, np.float64)
    n = len(w)
    dist = np.full(n, UNREACHED)
    dist[source] = 0.0
    done = np.zeros(n, bool)
    for _ in range(n):
        u = int(np.argmin(np.where(done, np.inf, dist)))
        if done[u] or dist[u] >= UNREACHED:
            break
        done[u] = True
        for v in range(n):
            if v != u and w[u, v] < UNREACHED and dist[u] + w[u, v] < dist[v]:
                dist[v] = dist[u] + w[u, v]
    return dist


def triangles(adj) -> int:
    nb = [set(x) for x in _neighbours(adj)]
    return sum(len(nb[u] & nb[v]) for u in range(len(nb)) for v in nb[u]
               if u < v) // 3


def betweenness(adj, source: int, matvec=None) -> np.ndarray:
    """Single-source Brandes dependencies (delta[source] = 0)."""
    a = np.asarray(adj, np.float64)
    level = bfs(adj, source)
    n = len(a)
    sigma = np.zeros(n)
    sigma[source] = 1.0
    for lv in range(1, level.max() + 1):
        on = level == lv
        prev = (level == lv - 1) * sigma
        paths = a.T @ prev if matvec is None else matvec(a.T, prev)
        sigma[on] = paths[on]
    delta = np.zeros(n)
    for lv in range(level.max(), 0, -1):
        on = level == lv
        coeff = np.where(on, (1.0 + delta) / np.maximum(sigma, 1e-300), 0.0)
        back = a @ coeff if matvec is None else matvec(a, coeff)
        delta += np.where(level == lv - 1, back * sigma, 0.0)
    delta[source] = 0.0
    return delta


def _bf16(x: np.ndarray) -> np.ndarray:
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def lowp_matvec(m: np.ndarray, x: np.ndarray, passes: int = 3) -> np.ndarray:
    """``m @ x`` as the TPU computes a float32 product at Precision.HIGH
    (``passes=3``: each operand split into a bfloat16 head and tail, the
    tail x tail term dropped) or DEFAULT (``passes=1``: the heads alone),
    with float32 accumulation."""
    m, x = m.astype(np.float32), x.astype(np.float32)
    mh, xh = _bf16(m), _bf16(x)
    out = mh @ xh
    if passes == 3:
        out = out + mh @ _bf16(x - xh) + _bf16(m - mh) @ xh
    return out.astype(np.float64)


def gap_suite(adj, w, c: dict, matvec=None) -> dict:
    """Every kernel's answer on one graph, as the configuration defines
    them."""
    src = c["source_node"]
    pr = pagerank(adj, c["pagerank_iters"], c["pagerank_damping"], matvec)
    out = {"bfs": bfs(adj, src), "cc": components(adj), "pagerank": pr,
           "sssp": sssp(w, src), "tc": triangles(adj),
           "bc": betweenness(adj, src, matvec)}
    out["summary"] = {
        "reached": int((out["bfs"] >= 0).sum()),
        "components": int(len(np.unique(out["cc"]))),
        "finite_paths": int((out["sssp"] < 1e8).sum()),
        "triangles": float(out["tc"]),
        "pr_mass": float(pr.sum()),
        "max_bc": float(out["bc"].max()),
    }
    return out

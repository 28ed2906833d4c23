"""Reference Brandes' algorithm (Algorithm 1 of the paper), pure numpy.

This is the correctness oracle for every other implementation in the
repository: the JAX single-device engine, the 2-D distributed engine and
all heuristic paths must match it to float tolerance.  O(nm); use on
small/medium graphs only.

Weighted graphs (``graph.w`` set) use the Dijkstra variant: the BFS
queue becomes a binary heap, the predecessor test becomes
``dist[w] == dist[v] + w_vw`` and the dependency sweep walks vertices in
descending settled-distance order (Brandes 2001, §4).
"""
from __future__ import annotations

import heapq
from collections import deque

import numpy as np

from repro.graphs.graph import Graph

__all__ = [
    "brandes_reference",
    "single_source_dependencies",
    "single_source_dependencies_csr",
    "single_source_dependencies_weighted",
]


def single_source_dependencies(
    adj: list[np.ndarray], n: int, s: int, dtype=np.float64
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One Brandes round from source ``s``.

    Returns (delta [n], sigma [n], depth [n]); depth is -1 off-component.
    """
    sigma = np.zeros(n, dtype=dtype)
    depth = np.full(n, -1, dtype=np.int64)
    sigma[s] = 1.0
    depth[s] = 0
    order: list[int] = []
    q: deque[int] = deque([s])
    while q:
        v = q.popleft()
        order.append(v)
        for w in adj[v]:
            if depth[w] < 0:
                depth[w] = depth[v] + 1
                q.append(w)
            if depth[w] == depth[v] + 1:
                sigma[w] += sigma[v]
    delta = np.zeros(n, dtype=dtype)
    for w in reversed(order):
        for v in adj[w]:
            if depth[v] == depth[w] - 1:
                delta[v] += sigma[v] / sigma[w] * (1.0 + delta[w])
    return delta, sigma, depth


def single_source_dependencies_csr(
    row_ptr: np.ndarray, col: np.ndarray, s: int, dtype=np.float64
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """:func:`single_source_dependencies` with numpy whole-level steps.

    The same Brandes round on a CSR adjacency (:meth:`Graph.csr`), for
    graphs with millions of arcs where the per-arc Python loop takes
    seconds per source: a frontier BFS assigns depths, then σ and δ are
    accumulated over the shortest-path DAG arcs one level at a time in
    float64.  Returns (delta [n], sigma [n], depth [n]) like the loop
    version, equal to it up to float64 summation order.
    """
    n = row_ptr.size - 1
    depth = np.full(n, -1, dtype=np.int64)
    depth[s] = 0
    frontier = np.array([s], dtype=np.int64)
    max_depth = 0
    while True:
        starts = row_ptr[frontier]
        counts = row_ptr[frontier + 1] - starts
        offs = np.repeat(starts - np.cumsum(counts) + counts, counts)
        nbrs = col[offs + np.arange(offs.size)]
        frontier = np.unique(nbrs[depth[nbrs] < 0]).astype(np.int64)
        if frontier.size == 0:
            break
        max_depth += 1
        depth[frontier] = max_depth
    src = np.repeat(np.arange(n), np.diff(row_ptr))
    lv, lw = depth[src], depth[col]
    on_dag = (lv >= 0) & (lw == lv + 1)
    order = np.argsort(lv[on_dag], kind="stable")
    v, w = src[on_dag][order], col[on_dag][order].astype(np.int64)
    bounds = np.searchsorted(lv[on_dag][order], np.arange(max_depth + 1))
    sigma = np.zeros(n, dtype=dtype)
    sigma[s] = 1.0
    for d in range(max_depth):  # σ of level d+1 from its level-d parents
        a, b = bounds[d], bounds[d + 1]
        sigma += np.bincount(w[a:b], weights=sigma[v[a:b]], minlength=n)
    delta = np.zeros(n, dtype=dtype)
    for d in reversed(range(max_depth)):  # δ of level d from level d+1
        a, b = bounds[d], bounds[d + 1]
        vv, ww = v[a:b], w[a:b]
        delta += np.bincount(
            vv, weights=sigma[vv] / sigma[ww] * (1.0 + delta[ww]), minlength=n
        )
    return delta, sigma, depth


def single_source_dependencies_weighted(
    wadj: list[tuple[np.ndarray, np.ndarray]], n: int, s: int, dtype=np.float64
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One weighted Brandes round from source ``s`` (Dijkstra forward).

    Returns (delta [n], sigma [n], dist [n]); dist is +inf off-component.
    """
    sigma = np.zeros(n, dtype=dtype)
    dist = np.full(n, np.inf, dtype=dtype)
    sigma[s] = 1.0
    dist[s] = 0.0
    settled = np.zeros(n, dtype=bool)
    order: list[int] = []
    heap: list[tuple[float, int]] = [(0.0, s)]
    while heap:
        dv, v = heapq.heappop(heap)
        if settled[v] or dv > dist[v]:
            continue
        settled[v] = True
        order.append(v)
        nbrs, ws = wadj[v]
        for w, wt in zip(nbrs, ws):
            cand = dist[v] + float(wt)
            if cand < dist[w]:
                dist[w] = cand
                sigma[w] = sigma[v]
                heapq.heappush(heap, (cand, int(w)))
            elif cand == dist[w] and not settled[w]:
                sigma[w] += sigma[v]
    delta = np.zeros(n, dtype=dtype)
    for w in reversed(order):
        nbrs, ws = wadj[w]
        for v, wt in zip(nbrs, ws):
            if dist[v] + float(wt) == dist[w] and sigma[w] > 0:
                delta[v] += sigma[v] / sigma[w] * (1.0 + delta[w])
    return delta, sigma, dist


def brandes_reference(
    graph: Graph, sources: np.ndarray | None = None, dtype=np.float64
) -> np.ndarray:
    """Exact betweenness centrality scores (unnormalized, ordered-pair
    convention: for undirected graphs every unordered pair contributes to
    both directions, as in the paper's Formula (1)).  Weighted graphs
    dispatch to the Dijkstra round automatically."""
    n = graph.n
    bc = np.zeros(n, dtype=dtype)
    if sources is None:
        sources = np.arange(n)
    if graph.w is not None:
        wadj = graph.weighted_adjacency_lists()
        for s in sources:
            delta, _, _ = single_source_dependencies_weighted(wadj, n, int(s), dtype=dtype)
            delta[int(s)] = 0.0
            bc += delta
        return bc
    adj = graph.adjacency_lists()
    for s in sources:
        delta, _, _ = single_source_dependencies(adj, n, int(s), dtype=dtype)
        delta[int(s)] = 0.0
        bc += delta
    return bc

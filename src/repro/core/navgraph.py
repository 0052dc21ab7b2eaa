"""In-memory navigation graph over posting-list centroids (paper §4.1).

SPTAG-flavoured incremental kNN-graph build: vertices are added one by one,
connected to their current top-R nearest, and neighbours back-update under a
max-degree cap.  Search is best-first beam search (the CPU stage ② of the
online pipeline), run for a scan window's queries in lockstep so that each
step scores all their new neighbours in one numpy call.  A device-side
``lax.while_loop`` variant exists for completeness (tests prove it
matches), but production placement is CPU, exactly as in the paper."""

from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class NavGraph:
    points: np.ndarray                 # (C, D) centroids
    neighbors: np.ndarray              # (C, R) int32, -1 padded
    entry: int                         # search entry point (medoid-ish)
    # SPTAG pairs the graph with space-partition TREES that provide seeds
    # for traversal; a kNN graph over tight clusters is otherwise a set of
    # disconnected cliques.  Stand-in with the same O(sqrt(C)) lookup and
    # geometric coverage: a 2-level k-means hierarchy over the vertices —
    # query -> nearest super-centroids -> their member vertices as seeds.
    super_centroids: Optional[np.ndarray] = None   # (S, D)
    super_assign: Optional[np.ndarray] = None      # (C,) vertex -> super

    def seed_beam(self, query: np.ndarray, n_super: int = 3,
                  per_super: int = 3) -> np.ndarray:
        """``seed_beams`` of one query."""
        return self.seed_beams(query[None], n_super, per_super)[0]

    def seed_beams(self, queries: np.ndarray, n_super: int = 3,
                   per_super: int = 3) -> List[np.ndarray]:
        """Seed vertices of each row of ``queries`` (B, D): the entry plus
        the ``per_super`` nearest members of each of the ``n_super``
        nearest super-centroids, ascending."""
        if self.super_centroids is None:
            return [np.array([self.entry], np.int64)] * len(queries)
        order, bounds = self._by_super
        ds = np.sum((self.super_centroids[None] - queries[:, None]) ** 2, -1)
        out = []
        for q, row in zip(queries, np.argsort(ds, -1)[:, :n_super].tolist()):
            seeds = [np.array([self.entry], np.int64)]
            for s in row:
                lo, hi = bounds[s], bounds[s + 1]
                if lo == hi:
                    continue
                d = np.sum((self.points[order[lo:hi]] - q) ** 2, -1)
                seeds.append(order[lo + np.argsort(d)[:per_super]])
            out.append(np.unique(np.concatenate(seeds)))
        return out

    @functools.cached_property
    def _by_super(self) -> Tuple[np.ndarray, List[int]]:
        """Vertices ordered by super-centroid, ascending within each (as
        ``np.where(super_assign == s)`` lists them), and each
        super-centroid's bounds in that order."""
        order = np.argsort(self.super_assign, kind="stable")
        counts = np.bincount(self.super_assign,
                             minlength=len(self.super_centroids))
        return order, [0] + np.cumsum(counts).tolist()


def _seed_tree(points: np.ndarray):
    """2-level k-means hierarchy (the SPTAG-tree stand-in)."""
    from repro.core.clustering import _kmeans
    c = len(points)
    s = max(2, int(np.ceil(np.sqrt(c))))
    rng = np.random.default_rng(0)
    supers = _kmeans(rng, points.astype(np.float32), s, iters=6)
    d2 = (np.sum(points ** 2, -1)[:, None] - 2.0 * points @ supers.T
          + np.sum(supers ** 2, -1)[None])
    return supers, np.argmin(d2, -1).astype(np.int32)


def knn_graph_exact(points: np.ndarray, degree: int = 32,
                    chunk: int = 2048) -> NavGraph:
    """Exact kNN graph via chunked brute force (fast path for <=50k points;
    used by the DiskANN-like baseline where graph quality, not build
    algorithm, is what matters)."""
    c = len(points)
    r = min(degree, c - 1)
    neighbors = np.empty((c, r), np.int32)
    norms = np.sum(points ** 2, -1)
    for s in range(0, c, chunk):
        blk = points[s:s + chunk]
        d2 = (np.sum(blk ** 2, -1)[:, None] - 2.0 * blk @ points.T
              + norms[None])
        d2[np.arange(len(blk)), s + np.arange(len(blk))] = np.inf
        idx = np.argpartition(d2, r - 1, axis=1)[:, :r]
        dd = np.take_along_axis(d2, idx, axis=1)
        neighbors[s:s + chunk] = np.take_along_axis(
            idx, np.argsort(dd, axis=1), axis=1)
    entry = int(np.argmin(np.sum(
        (points - points.mean(0, keepdims=True)) ** 2, -1)))
    supers, assign = _seed_tree(points)
    return NavGraph(points=points.astype(np.float32), neighbors=neighbors,
                    entry=entry, super_centroids=supers, super_assign=assign)


def build_navgraph(points: np.ndarray, degree: int = 32,
                   ef_build: int = 64) -> NavGraph:
    """Navigation-graph construction.

    <=50k vertices (every config in this repo; SPANN keeps the centroid
    count at a RAM-friendly fraction of N): exact kNN adjacency — highest
    quality, BLAS-fast.  Beyond that, SPTAG-style incremental insertion
    where each vertex links to its top-``degree`` nearest found by seeded
    graph search over the partial graph (kept for the 100M-centroid scale
    where O(C^2) is impossible)."""
    if len(points) <= 50_000:
        return knn_graph_exact(points.astype(np.float32), degree=degree)
    c, d = points.shape
    r = min(degree, max(c - 1, 1))
    nbrs: List[List[Tuple[float, int]]] = [[] for _ in range(c)]

    def link(u: int, v: int, dist: float) -> None:
        lst = nbrs[u]
        heapq.heappush(lst, (-dist, v))
        if len(lst) > r:
            heapq.heappop(lst)             # drop farthest

    bootstrap = min(c, 2 * r)
    for i in range(1, c):
        if i <= bootstrap:
            cand = np.arange(i)
        else:
            cand = _search_ids(points, nbrs, points[i], ef_build, entry=0)
        dd = np.sum((points[cand] - points[i]) ** 2, -1)
        order = np.argsort(dd)[:r]
        for j in order:
            v, dist = int(cand[j]), float(dd[j])
            link(i, v, dist)
            link(v, i, dist)

    neighbors = np.full((c, r), -1, np.int32)
    for i, lst in enumerate(nbrs):
        ids = [v for _, v in sorted(lst, reverse=True)]
        neighbors[i, :len(ids)] = ids[:r]
    entry = int(np.argmin(np.sum(
        (points - points.mean(0, keepdims=True)) ** 2, -1)))
    supers, assign = _seed_tree(points)
    return NavGraph(points=points, neighbors=neighbors, entry=entry,
                    super_centroids=supers, super_assign=assign)


def _search_ids(points, nbrs_dyn, query, ef, entry=0) -> np.ndarray:
    """Best-first search over the under-construction adjacency (build helper)."""
    visited = {entry}
    d0 = float(np.sum((points[entry] - query) ** 2))
    cand = [(d0, entry)]
    best = [(-d0, entry)]
    while cand:
        dist, u = heapq.heappop(cand)
        if dist > -best[0][0] and len(best) >= ef:
            break
        for _, v in nbrs_dyn[u]:
            if v in visited:
                continue
            visited.add(v)
            dv = float(np.sum((points[v] - query) ** 2))
            if len(best) < ef or dv < -best[0][0]:
                heapq.heappush(cand, (dv, v))
                heapq.heappush(best, (-dv, v))
                if len(best) > ef:
                    heapq.heappop(best)
    return np.array([v for _, v in best], np.int64)


def search(graph: NavGraph, query: np.ndarray, top_m: int,
           ef: Optional[int] = None) -> np.ndarray:
    """CPU best-first beam search -> ids of the top-m nearest centroids
    (online stage ②).  ef defaults to 2*top_m."""
    return search_batch(graph, query[None], [top_m], ef)[0][0]


def search_batch(graph: NavGraph, queries: np.ndarray,
                 top_ms: Sequence[int], ef: Optional[int] = None
                 ) -> Tuple[List[np.ndarray], List[int]]:
    """``search`` for each row of ``queries`` (B, D), the B searches run in
    lockstep -> (each query's ids, the vertices each query expanded).
    Each step, every live query pops its own best candidate; one gather
    and one row-wise distance reduction then score the unvisited
    neighbours of all popped vertices, and each query's heaps take them in
    neighbour order.  Per query that is the same search, pop for pop, with
    the same float32 distances."""
    points, neighbors = graph.points, graph.neighbors
    b = len(queries)
    efs = [ef or max(2 * m, 32) for m in top_ms]
    visited = [bytearray(len(points)) for _ in range(b)]
    cand: List[List[Tuple[float, int]]] = [[] for _ in range(b)]
    best: List[List[Tuple[float, int]]] = [[] for _ in range(b)]
    seeds = graph.seed_beams(queries)
    owner = np.repeat(np.arange(b), [len(s) for s in seeds])
    ids = np.concatenate(seeds)
    d0 = np.sum((points[ids] - queries[owner]) ** 2, -1)
    for qi, u, d in zip(owner.tolist(), ids.tolist(), d0.tolist()):
        visited[qi][u] = 1
        heapq.heappush(cand[qi], (d, u))
        heapq.heappush(best[qi], (-d, u))
    n_exp = [0] * b
    live = list(range(b))
    while live:
        # pop each live query's best candidate and gather its unvisited
        # neighbours, in neighbour order
        expanding, vs, vo = [], [], []
        for qi in live:
            if not cand[qi]:
                continue
            dist, u = heapq.heappop(cand[qi])
            if len(best[qi]) >= efs[qi] and dist > -best[qi][0][0]:
                continue
            n_exp[qi] += 1
            expanding.append(qi)
            seen = visited[qi]
            for v in neighbors[u].tolist():
                if v >= 0 and not seen[v]:
                    seen[v] = 1
                    vs.append(v)
                    vo.append(qi)
        live = expanding
        if not vs:
            continue
        dv = np.sum((points[vs] - queries[vo]) ** 2, -1).tolist()
        last = -1
        for qi, v, d in zip(vo, vs, dv):
            if qi != last:
                bq, cq, e, last = best[qi], cand[qi], efs[qi], qi
            if len(bq) < e or d < -bq[0][0]:
                heapq.heappush(cq, (d, v))
                heapq.heappush(bq, (-d, v))
                if len(bq) > e:
                    heapq.heappop(bq)
    out = []
    for qi in range(b):
        ranked = sorted((-nd, v) for nd, v in best[qi])
        out.append(np.array([v for _, v in ranked[:top_ms[qi]]], np.int32))
    return out, n_exp


def search_jax(points: jax.Array, neighbors: jax.Array, entry: int,
               query: jax.Array, top_m: int, max_steps: int = 64,
               seeds: Optional[jax.Array] = None):
    """Device-side best-first search (bounded ``lax.while_loop``) keeping a
    fixed-size beam.  Semantically matches ``search`` up to beam ties."""
    c, r = neighbors.shape
    ef = max(2 * top_m, 32)

    def dist_to(idx):
        return jnp.sum((points[idx] - query) ** 2, -1)

    if seeds is not None:
        sd = dist_to(seeds)
        neg, pos = jax.lax.top_k(-sd, min(4, seeds.shape[0]))
        init = jnp.concatenate(
            [seeds[pos].astype(jnp.int32), jnp.asarray([entry], jnp.int32)])
    else:
        init = jnp.asarray([entry], jnp.int32)
    n0 = init.shape[0]
    beam_ids = jnp.full((ef,), entry, jnp.int32).at[:n0].set(init)
    beam_d = jnp.full((ef,), jnp.inf, jnp.float32).at[:n0].set(dist_to(init))
    expanded = jnp.zeros((ef,), bool)
    visited = jnp.zeros((c,), bool).at[init].set(True)

    def cond(state):
        beam_ids, beam_d, expanded, visited, steps = state
        frontier = jnp.logical_and(~expanded, jnp.isfinite(beam_d))
        return jnp.logical_and(steps < max_steps, jnp.any(frontier))

    def body(state):
        beam_ids, beam_d, expanded, visited, steps = state
        masked = jnp.where(expanded, jnp.inf, beam_d)
        u_slot = jnp.argmin(masked)
        u = beam_ids[u_slot]
        expanded = expanded.at[u_slot].set(True)
        nb = neighbors[u]                                    # (R,)
        valid = jnp.logical_and(nb >= 0, ~visited[jnp.maximum(nb, 0)])
        nd = jnp.where(valid, dist_to(jnp.maximum(nb, 0)), jnp.inf)
        visited = visited.at[jnp.maximum(nb, 0)].set(
            jnp.logical_or(visited[jnp.maximum(nb, 0)], valid))
        # merge beam with the R candidates, keep best ef
        all_d = jnp.concatenate([beam_d, nd])
        all_i = jnp.concatenate([beam_ids, nb.astype(jnp.int32)])
        all_e = jnp.concatenate([expanded, jnp.zeros((r,), bool)])
        neg, pos = jax.lax.top_k(-all_d, ef)
        return (all_i[pos], -neg, all_e[pos], visited, steps + 1)

    beam_ids, beam_d, expanded, visited, _ = jax.lax.while_loop(
        cond, body, (beam_ids, beam_d, expanded, visited, 0))
    neg, pos = jax.lax.top_k(-beam_d, top_m)
    return beam_ids[pos], -neg

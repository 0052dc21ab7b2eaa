"""Navigation graph build + search quality."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import navgraph as ng
from repro.data.synthetic import clustered_vectors


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(0)
    pts = clustered_vectors(rng, 400, 16, n_clusters=12)
    return pts, ng.build_navgraph(pts, degree=16)


def test_graph_structure(graph):
    pts, g = graph
    assert g.neighbors.shape[0] == 400
    assert (g.neighbors < 400).all()
    # every non-entry vertex has at least one neighbour
    assert ((g.neighbors >= 0).sum(1)[1:] >= 1).all()


def test_search_recall_vs_bruteforce(graph):
    pts, g = graph
    rng = np.random.default_rng(1)
    hits, total = 0, 0
    for _ in range(20):
        q = pts[rng.integers(0, 400)] + 0.05 * rng.standard_normal(16) \
            .astype(np.float32)
        found = ng.search(g, q, top_m=10)
        exact = np.argsort(np.sum((pts - q) ** 2, -1))[:10]
        hits += len(set(found.tolist()) & set(exact.tolist()))
        total += 10
    assert hits / total >= 0.85


def test_search_returns_sorted_by_distance(graph):
    pts, g = graph
    q = pts[7]
    found = ng.search(g, q, top_m=8)
    d = np.sum((pts[found] - q) ** 2, -1)
    assert (np.diff(d) >= -1e-5).all()


def test_jax_search_matches_host_quality(graph):
    pts, g = graph
    rng = np.random.default_rng(2)
    q = pts[rng.integers(0, 400)] + 0.05 * rng.standard_normal(16) \
        .astype(np.float32)
    ids_host = ng.search(g, q, top_m=10)
    seeds = jnp.arange(0, len(pts), 8)      # stratified device-side seeds
    ids_dev, _ = ng.search_jax(jnp.asarray(pts), jnp.asarray(g.neighbors),
                               g.entry, jnp.asarray(q), 10, seeds=seeds)
    exact = set(np.argsort(np.sum((pts - q) ** 2, -1))[:10].tolist())
    dev_hits = len(set(np.asarray(ids_dev).tolist()) & exact)
    host_hits = len(set(ids_host.tolist()) & exact)
    assert dev_hits >= host_hits - 3      # same ballpark quality


# ---------------------------------------------------------------------------
# Lockstep window search against the per-query, per-neighbour search it
# replaced, kept here verbatim (plus an expansion counter) as the oracle
# ---------------------------------------------------------------------------

def oracle_seed_beam(graph, query, n_super=3, per_super=3):
    if graph.super_centroids is None:
        return np.array([graph.entry], np.int64)
    ds = np.sum((graph.super_centroids - query) ** 2, -1)
    out = [np.array([graph.entry], np.int64)]
    for s in np.argsort(ds)[:n_super]:
        members = np.where(graph.super_assign == s)[0]
        if not len(members):
            continue
        dm = np.sum((graph.points[members] - query) ** 2, -1)
        out.append(members[np.argsort(dm)[:per_super]])
    return np.unique(np.concatenate(out))


def oracle_search(graph, query, top_m, ef=None):
    """-> (ids of the top-m nearest centroids, vertices expanded)."""
    import heapq
    ef = ef or max(2 * top_m, 32)
    points, neighbors = graph.points, graph.neighbors
    visited = np.zeros(len(points), bool)
    cand, best = [], []
    expanded = 0
    for entry in oracle_seed_beam(graph, query):
        entry = int(entry)
        visited[entry] = True
        d0 = float(np.sum((points[entry] - query) ** 2))
        heapq.heappush(cand, (d0, entry))
        heapq.heappush(best, (-d0, entry))
    while cand:
        dist, u = heapq.heappop(cand)
        if len(best) >= ef and dist > -best[0][0]:
            break
        expanded += 1
        for v in neighbors[u]:
            if v < 0 or visited[v]:
                continue
            visited[v] = True
            dv = float(np.sum((points[v] - query) ** 2))
            if len(best) < ef or dv < -best[0][0]:
                heapq.heappush(cand, (dv, v))
                heapq.heappush(best, (-dv, v))
                if len(best) > ef:
                    heapq.heappop(best)
    out = sorted(((-nd, v) for nd, v in best))
    return np.array([v for _, v in out[:top_m]], np.int32), expanded


def _near_points(g, rng, b):
    return (g.points[rng.integers(0, len(g.points), b)]
            + 0.3 * rng.standard_normal((b, g.points.shape[1]))
            ).astype(np.float32)


def _knn_graph():
    rng = np.random.default_rng(3)
    pts = clustered_vectors(rng, 600, 24, n_clusters=20)
    return ng.knn_graph_exact(pts, degree=12), _near_points


def _padded_graph():
    """Rows of 2-9 neighbours, -1 padded, some with an id repeated; no
    seed hierarchy, so every search starts from ``entry`` alone."""
    rng = np.random.default_rng(4)
    c, r = 300, 10
    pts = rng.standard_normal((c, 8)).astype(np.float32)
    nb = np.full((c, r), -1, np.int32)
    for u in range(c):
        row = rng.choice(c, rng.integers(2, r), replace=False)
        if u % 7 == 0:
            row = np.append(row, row[0])           # a repeat
        nb[u, :len(row)] = row
    return ng.NavGraph(points=pts, neighbors=nb, entry=0), _near_points


def _sphere_graph():
    """Vertices on a sphere, queries within 1e-5 of its centre: distances
    differ by a few ulps or tie exactly, so only the same float32
    arithmetic, and the same tie rule, give the same lists."""
    rng = np.random.default_rng(5)
    u = rng.standard_normal((300, 16))
    pts = (3 * u / np.linalg.norm(u, axis=1, keepdims=True)).astype(
        np.float32)

    def near_centre(g, rng, b):
        return (1e-5 * rng.standard_normal((b, 16))).astype(np.float32)
    return ng.knn_graph_exact(pts, degree=12), near_centre


@pytest.mark.parametrize("make_graph", [_knn_graph, _padded_graph,
                                        _sphere_graph],
                         ids=["knn_seeded", "padded_entry_only",
                              "sphere_near_ties"])
@pytest.mark.parametrize("b", [1, 3, 8])
def test_search_batch_matches_per_query_search(make_graph, b):
    g, make_queries = make_graph()
    rng = np.random.default_rng(b)
    top_ms = [int(m) for m in rng.choice([1, 5, 16, 40], b)]
    for _ in range(4):
        queries = make_queries(g, rng, b)
        got, expansions = ng.search_batch(g, queries, top_ms)
        for q, m, ids, n in zip(queries, top_ms, got, expansions):
            want, want_n = oracle_search(g, q, m)
            assert ids.dtype == want.dtype
            np.testing.assert_array_equal(ids, want)
            assert n == want_n > 0
            np.testing.assert_array_equal(ng.search(g, q, m), want)

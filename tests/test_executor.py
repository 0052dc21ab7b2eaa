"""Unified QueryExecutor: the three public query paths are one pipeline.

Contract under test (ISSUE 1 acceptance):
* ``query`` / ``batch_query`` / ``query_batch_fused`` return IDENTICAL ids
  (not merely similar recall) on a fixed seed — they are windows of the
  same stage list;
* the mesh-sharded ADC scan (>= 2 devices via the host platform override)
  matches the single-device scan exactly;
* window splitting and rerank/scan overlap never change results;
* shared QueryStats accounting invariants hold at every window size.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import recall_at_k
from repro.core.executor import QueryPlan


@pytest.fixture(scope="module")
def paths(anns_bundle):
    b = anns_bundle
    single = [b.index.query(q) for q in b.queries]
    batch = b.index.batch_query(b.queries)
    fused = b.index.query_batch_fused(b.queries)
    return b, single, batch, fused


def test_three_paths_identical_ids(paths):
    b, single, batch, fused = paths
    for s, bb, f in zip(single, batch, fused):
        np.testing.assert_array_equal(s.ids, bb.ids)
        np.testing.assert_array_equal(s.ids, f.ids)
        np.testing.assert_allclose(s.dists, f.dists, rtol=0, atol=0)


def test_three_paths_recall(paths):
    b, single, batch, fused = paths
    recs = [recall_at_k(np.stack([r.ids for r in res]), b.gt, 10)
            for res in (single, batch, fused)]
    assert all(r >= 0.90 for r in recs)
    assert max(recs) - min(recs) < 1e-9     # identical ids => identical recall


def test_window_and_overlap_parity(paths):
    b, single, batch, fused = paths
    for window, overlap in ((4, False), (4, True), (7, True)):
        res = b.index.executor.run(
            b.queries, b.index.plan(window=window, overlap_rerank=overlap))
        for f, r in zip(single, res):
            np.testing.assert_array_equal(f.ids, r.ids)


def test_stats_accounting_invariants(paths):
    b, single, batch, fused = paths
    for s in single:        # window of 1: ids-only H2D, own candidates only
        assert s.stats.h2d_bytes == 4 * s.stats.candidates_scanned
    u = fused[0].stats.candidates_scanned
    assert all(f.stats.candidates_scanned == u for f in fused)
    # inter-query dedup: union scanned once < sum of per-query scans
    assert u < sum(s.stats.candidates_scanned for s in single)
    B = len(fused)
    assert fused[0].stats.h2d_bytes == 4 * u // B


def test_masked_topk_batch_matches_reference(rng):
    """The executor's single-device bucket scan == brute ref over the
    gathered bucket rows."""
    from repro.core.distributed import sharded_adc_topn_window
    from repro.kernels.pq_adc.ref import pq_adc_batch_ref
    from repro.models.layers import ShardCtx
    codes = jnp.asarray(rng.integers(0, 256, (2048, 8)), jnp.uint8)
    rows = jnp.asarray(rng.choice(2048, 512, replace=False), jnp.int32)
    luts = jnp.asarray(rng.random((3, 8, 256)), jnp.float32)
    mask = jnp.asarray(rng.random((3, 512)) < 0.5)
    vals, pos = sharded_adc_topn_window(codes, rows, luts, mask, 32,
                                        ShardCtx())
    ref = np.asarray(pq_adc_batch_ref(codes[rows], luts))
    ref = np.where(np.asarray(mask), ref, np.inf)
    for qb in range(3):
        expect = np.sort(ref[qb])[:32]
        np.testing.assert_allclose(np.sort(np.asarray(vals[qb])), expect,
                                   rtol=1e-6)
        np.testing.assert_allclose(
            np.sort(ref[qb][np.asarray(pos[qb])]), expect, rtol=1e-6)


_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, sys.argv[1])
import dataclasses, json
import numpy as np
from repro.configs.anns_datasets import SIFT_SMALL
from repro.core.engine import FusionANNSIndex
from repro.data.synthetic import clustered_vectors
from repro.launch.mesh import make_test_mesh

rng = np.random.default_rng(0)
cfg = dataclasses.replace(SIFT_SMALL, n_vectors=800, dim=32,
                          n_posting_fraction=0.02)
data = clustered_vectors(rng, 808, 32, n_clusters=8)
index = FusionANNSIndex.build(data[:800], cfg)
queries = data[800:]

base = index.query_batch_fused(queries)
index.executor.attach_mesh(make_test_mesh(2))
assert index.executor._n_shards() == 2
sharded = index.query_batch_fused(queries)
singles = [index.query(q) for q in queries]     # sharded window-of-1

out = {"ids_exact": True, "dists_exact": True, "single_exact": True}
for b, s, one in zip(base, sharded, singles):
    out["ids_exact"] &= bool(np.array_equal(b.ids, s.ids))
    out["dists_exact"] &= bool(np.array_equal(b.dists, s.dists))
    out["single_exact"] &= bool(np.array_equal(b.ids, one.ids))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_results():
    """mesh >= 2 needs the host platform override BEFORE jax import."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"    # a CPU rehearsal: never reach for a chip
    proc = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, os.path.abspath(src)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("key", ["ids_exact", "dists_exact", "single_exact"])
def test_sharded_scan_matches_single_device(sharded_results, key):
    assert sharded_results[key], sharded_results


# ---------------------------------------------------------------------------
# Stage ①: a window's queries search the graph in lockstep
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def attributed(anns_bundle):
    from repro.core.engine import FusionANNSIndex
    b = anns_bundle
    return FusionANNSIndex.build(
        b.data, b.cfg, attributes={"cat": np.arange(len(b.data)) % 4})


def test_lockstep_window_collects_what_each_query_collects_alone(
        anns_bundle, attributed):
    from test_navgraph import oracle_search
    from repro.core.executor import PlanOverrides
    from repro.core.filters import Eq
    queries = np.asarray(anns_bundle.queries[:8], np.float32)
    overrides = [PlanOverrides(top_m=m, filter=Eq("cat", 1) if i == 5
                               else None)
                 for i, m in enumerate([16, 4, 24, 8, 16, 12, 2, 30])]
    ex = attributed.executor
    plans = [o.merge_into(attributed.plan(window=8)) for o in overrides]
    with ex._dispatch_lock:
        w = ex._dispatch(queries, plans)
    for q, p, ids, n in zip(queries, plans, w.per_q, w.collected):
        want, pre = w.view.collect_candidates(q, p.top_m, filt=p.filter)
        np.testing.assert_array_equal(ids, want)
        assert n == len(pre)                     # before the predicate
    assert 0 < len(w.per_q[5]) < len(w.view.collect_candidates(
        queries[5], plans[5].top_m)[0])              # the filter bit
    answers = {window: ex.submit(queries, attributed.plan(window=window),
                                 overrides=overrides).results()
               for window in (1, 8)}
    for q, p, one, eight in zip(queries, plans, answers[1], answers[8]):
        np.testing.assert_array_equal(one.ids, eight.ids)
        _, want_n = oracle_search(attributed.graph, q, p.top_m)
        assert one.stats.graph_expansions == eight.stats.graph_expansions \
            == want_n > 0


# ------------------------------------------------- collection counters

def _lists_and_rows(view, q, top_m):
    from repro.core import navgraph as ng
    cids = ng.search(view.graph, np.asarray(q, np.float32), top_m)
    return cids, [view.posting.members[c] for c in cids]


def test_collection_counters_are_exact(fresh_index, anns_bundle):
    """``lists_empty`` and ``candidates_collected`` against a count made
    here: on the built index (no list empty), then on a view with one of
    a query's lists emptied and another one's rows all tombstoned."""
    import dataclasses
    from repro.core.clustering import PostingLists
    ix, queries = fresh_index, anns_bundle.queries
    top_m = ix.cfg.top_m
    for q, r in zip(queries, ix.batch_query(queries)):
        _, lists = _lists_and_rows(ix.view(), q, top_m)
        assert r.stats.lists_empty == 0
        assert r.stats.candidates_collected == len(
            np.unique(np.concatenate(lists)))

    q = queries[0]
    view = ix.view()
    cids, lists = _lists_and_rows(view, q, top_m)
    members = list(view.posting.members)
    members[cids[1]] = members[cids[1]][:0]
    ix._view = dataclasses.replace(view, posting=PostingLists(
        view.posting.centroids, members, view.posting.primary))
    ix.delete(members[cids[0]])
    tomb = ix.view().tombstones

    def want(q):
        cids, _ = _lists_and_rows(ix.view(), q, top_m)
        rows = np.unique(np.concatenate([members[c] for c in cids]))
        return (sum(not np.any(~tomb[members[c]]) for c in cids),
                int((~tomb[rows]).sum()))
    assert want(q)[0] >= 2
    wants = [want(x) for x in queries[:4]]
    for rs in ([ix.query(q)], ix.batch_query(queries[:4]),
               ix.query_batch_fused(queries[:4])):
        for r, (empty, collected) in zip(rs, wants):
            assert r.stats.lists_empty == empty
            assert r.stats.candidates_collected == collected


def test_rollups_sum_the_collection_counters(anns_bundle):
    from repro.serve.anns_service import BatchingANNSService
    from repro.serve.client import SearchRequest
    from repro.serve.router import ReplicaRouter
    b = anns_bundle
    for backend in (BatchingANNSService(b.index, max_batch=4,
                                        max_wait_s=0.0),
                    ReplicaRouter(b.index, n_replicas=2,
                                  policy="round_robin", threaded=False,
                                  max_batch=4, max_wait_s=0.0)):
        futs = [backend.submit(SearchRequest(query=q)) for q in b.queries]
        backend.drain()
        stats = [f.result().stats for f in futs]
        roll = backend.stats_rollup()["query_stats"]
        for field in ("lists_empty", "candidates_collected"):
            assert roll[field] == sum(getattr(s, field) for s in stats)
        assert roll["candidates_collected"] >= len(b.queries) * 10
        backend.stop()


# ------------------------------------------------ stage ⑦ in lockstep

# counters a window gives each of its queries from its shared union
_WINDOW_SHARED = ("h2d_bytes", "candidates_scanned", "candidates_prefilter")


def _each_alone(monkeypatch):
    """Stage ⑦ with every query of a lockstep group re-ranked on its own,
    the results stacked back into the group's block."""
    import repro.core.executor as executor_mod
    from repro.core.rerank import RerankResult
    real = executor_mod.heuristic_rerank

    def alone(queries, rows, ssd, k, **kw):
        parts = [real(queries[j:j + 1], [r], ssd, k, **kw)
                 for j, r in enumerate(rows)]
        stack = {f: np.concatenate([getattr(p, f) for p in parts])
                 for f in ("ids", "dists", "batches_run",
                           "candidates_scored", "early_stopped")}
        return RerankResult(io=[p.io[0] for p in parts], **stack)
    monkeypatch.setattr(executor_mod, "heuristic_rerank", alone)


@pytest.mark.parametrize("reference", ["window_of_one", "each_alone"])
def test_lockstep_rerank_answers_as_each_query_alone(anns_bundle,
                                                     monkeypatch, reference):
    """A window of 8 with mixed ``k`` / ``top_n`` re-ranks in lockstep
    groups; its answers and counters equal the same queries' at window 1
    (all but the three counters a window shares out from its union) and,
    in the same window, with each query re-ranked on its own (all)."""
    from repro.core.executor import QUERY_STATS_FIELDS, PlanOverrides
    b = anns_bundle
    ex = b.index.executor
    queries = np.asarray(b.queries[:8], np.float32)
    overrides = [PlanOverrides(k=k, top_n=n) for k, n in
                 [(10, None), (5, 64), (10, 100), (3, None), (5, 16),
                  (10, 200), (1, 40), (10, 96)]]
    got = ex.submit(queries, b.index.plan(window=8),
                    overrides=overrides).results()
    fields = QUERY_STATS_FIELDS
    if reference == "each_alone":
        _each_alone(monkeypatch)
        want = ex.submit(queries, b.index.plan(window=8),
                         overrides=overrides).results()
    else:
        want = ex.submit(queries, b.index.plan(window=1),
                         overrides=overrides).results()
        fields = [f for f in fields if f not in _WINDOW_SHARED]
    for g, w, o in zip(got, want, overrides):
        assert len(g.ids) == o.k
        np.testing.assert_array_equal(g.ids, w.ids)
        np.testing.assert_array_equal(g.dists, w.dists)
        for f in fields:
            assert getattr(g.stats, f) == getattr(w.stats, f), f
        assert g.stats.early_stopped == w.stats.early_stopped
    # the k = 10 group of four ran together at some step
    assert max(g.stats.rerank_width for g in got) > 1.0
    if reference == "window_of_one":
        assert all(w.stats.rerank_width == 1.0 for w in want)


def test_cancelled_and_expired_rows_skip_only_their_own_rerank(
        anns_bundle, monkeypatch):
    import repro.core.executor as executor_mod
    from repro.core.executor import PlanOverrides
    from repro.core.futures import DeadlineExceeded
    b = anns_bundle
    ex = b.index.executor
    queries = np.asarray(b.queries[:8], np.float32)
    plan = b.index.plan(window=8)
    want = ex.submit(queries, plan).results()
    seen = []
    real = executor_mod.heuristic_rerank

    def recording(block, rows, ssd, k, **kw):
        seen.append(np.array(block))
        return real(block, rows, ssd, k, **kw)
    monkeypatch.setattr(executor_mod, "heuristic_rerank", recording)
    overrides = [None] * 8
    overrides[5] = PlanOverrides(deadline_s=0.0)
    ticket = ex.submit(queries, plan, overrides=overrides)
    assert ticket.futures[2].cancel()           # scan in flight, not landed
    ticket.wait()
    assert ticket.futures[2].cancelled()
    with pytest.raises(DeadlineExceeded):
        ticket.futures[5].result()
    kept = [0, 1, 3, 4, 6, 7]
    assert len(seen) == 1                        # one lockstep group
    np.testing.assert_array_equal(seen[0], queries[kept])
    for qi in kept:
        r = ticket.futures[qi].result()
        np.testing.assert_array_equal(r.ids, want[qi].ids)
        np.testing.assert_array_equal(r.dists, want[qi].dists)


def test_rerank_width_counts_the_queries_scored_together(anns_bundle):
    """With no early stop a query runs top_n / rerank_batch mini-batches:
    2, 1, 3 and 1 here, so its group of four takes 3 lockstep steps for 7
    mini-batches, 7/3 queries a step; alone, each is 1."""
    from repro.core.executor import PlanOverrides
    b = anns_bundle
    ex = b.index.executor
    queries = np.asarray(b.queries[:4], np.float32)
    for window, width in ((4, 7 / 3), (1, 1.0)):
        plan = b.index.plan(window=window).override(disable_early_stop=True)
        overrides = [PlanOverrides(top_n=n * plan.rerank_batch)
                     for n in (2, 1, 3, 1)]
        res = ex.submit(queries, plan, overrides=overrides).results()
        assert [r.stats.rerank_batches for r in res] == [2, 1, 3, 1]
        assert [r.stats.rerank_width for r in res] == [width] * 4

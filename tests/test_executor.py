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
    for q, p, ids in zip(queries, plans, w.per_q):
        want, _ = w.view.collect_candidates(q, p.top_m, filt=p.filter)
        np.testing.assert_array_equal(ids, want)
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

"""End-to-end FusionANNS engine: recall, the paper's I/O claims at reduced
scale, and technique ablations (Fig. 12 shape)."""

import numpy as np
import pytest

from repro.core.baselines import HIPq, RummyLike, SpannLike
from repro.core.engine import FusionANNSIndex, recall_at_k


def _unpack(b):
    return b.cfg, b.data, b.index, b.queries, b.gt


@pytest.fixture(scope="module")
def setup(anns_bundle):
    return _unpack(anns_bundle)   # session-scoped shared index (conftest.py)


@pytest.fixture(scope="module")
def shaped(any_shape):
    """The shared index at the tier-1 shape and at each served shape."""
    return _unpack(any_shape)


def test_recall_meets_paper_bar(shaped):
    cfg, data, index, queries, gt = shaped
    res = index.batch_query(queries)
    rec = recall_at_k(np.stack([r.ids for r in res]), gt, 10)
    assert rec >= 0.90        # paper's operating point Recall@10 >= 0.9


def test_h2d_is_ids_only(shaped):
    """Multi-tiered index invariant: host->device traffic is 4 B per
    candidate id, never vector payload."""
    cfg, data, index, queries, gt = shaped
    r = index.query(queries[0])
    assert r.stats.h2d_bytes == 4 * r.stats.candidates_scanned
    # SPANN-equivalent would ship whole posting lists (>= dim bytes/vec)
    assert r.stats.h2d_bytes < r.stats.candidates_scanned * cfg.dim


def test_fusionanns_fewer_ios_than_spann(setup):
    """Fig. 12c: multi-tiered indexing cuts I/O vs SPANN (3.2-3.8x at 1B;
    directionally at reduced scale)."""
    cfg, data, index, queries, gt = setup
    spann = SpannLike(index, data)
    f_ios = np.mean([index.query(q).stats.ios for q in queries])
    s_ios = np.mean([spann.query(q, 10, cfg.top_m).io.pages_requested
                     for q in queries])
    assert f_ios < s_ios


def test_heuristic_rerank_cuts_ios(shaped):
    cfg, data, index, queries, gt = shaped
    with_hr = [index.query(q) for q in queries]
    without = [index.query(q, disable_early_stop=True) for q in queries]
    assert (np.mean([r.stats.ios for r in with_hr])
            <= np.mean([r.stats.ios for r in without]))
    # accuracy preserved
    rec_hr = recall_at_k(np.stack([r.ids for r in with_hr]), gt, 10)
    rec_full = recall_at_k(np.stack([r.ids for r in without]), gt, 10)
    assert rec_hr >= rec_full - 0.05


def test_dedup_cuts_ios(shaped):
    cfg, data, index, queries, gt = shaped
    no_dedup = FusionANNSIndex(
        cfg=index.cfg, codebook=index.codebook, codes=index.codes,
        posting=index.posting, graph=index.graph,
        ssd=_clone_ssd(index, intra=False, buf=False))
    ios_opt = np.mean([index.query(q).stats.ios for q in queries])
    ios_raw = np.mean([no_dedup.query(q).stats.ios for q in queries])
    assert ios_opt <= ios_raw


def _clone_ssd(index, intra, buf):
    from repro.core.io_sim import SSDSim
    return SSDSim(index.ssd.vectors, index.ssd.layout,
                  buffer_pages=index.cfg.dram_buffer_pages,
                  intra_merge=intra, use_buffer=buf)


def test_baselines_reach_similar_recall(setup):
    """All systems searched with the same top_m must find similar
    neighbours (they share the IVF index)."""
    cfg, data, index, queries, gt = setup
    spann = SpannLike(index, data)
    rummy = RummyLike(index, data)
    r_s = np.stack([spann.query(q, 10, cfg.top_m).ids for q in queries])
    r_r = np.stack([rummy.query(q, 10, cfg.top_m).ids for q in queries])
    assert recall_at_k(r_s, gt, 10) >= 0.9
    assert recall_at_k(r_r, gt, 10) >= 0.9


def test_rummy_moves_vectors_fusionanns_moves_ids(setup):
    cfg, data, index, queries, gt = setup
    rummy = RummyLike(index, data)
    rd = rummy.query(queries[0], 10, cfg.top_m).demand
    fr = index.query(queries[0]).stats
    assert rd.h2d_bytes > fr.h2d_bytes      # PCIe traffic gap (Fig. 4d)


def test_fused_batch_matches_per_query(shaped):
    """Beyond-paper fused batch scan returns the same neighbours as the
    per-query path, while scanning the candidate UNION once."""
    cfg, data, index, queries, gt = shaped
    per = index.batch_query(queries[:8])
    fused = index.query_batch_fused(queries[:8])
    r_per = recall_at_k(np.stack([r.ids for r in per]), gt[:8], 10)
    r_fused = recall_at_k(np.stack([r.ids for r in fused]), gt[:8], 10)
    assert r_fused >= r_per - 0.03
    # inter-query dedup: union scanned once < sum of per-query scans
    union_scans = fused[0].stats.candidates_scanned      # same for all
    total_per = sum(r.stats.candidates_scanned for r in per)
    assert union_scans < total_per

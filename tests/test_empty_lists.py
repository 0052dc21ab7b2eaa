"""An index whose clustering leaves empty posting lists: the build drops
them, so a query whose ``top_m`` they would have filled still collects
candidates, and the seal path and snapshots keep the kept lists' ids."""

import copy
import dataclasses

import numpy as np
import pytest
from _plant import planting

from repro.configs.anns_datasets import SIFT_SMALL
from repro.core import clustering
from repro.core.engine import FusionANNSIndex, ground_truth, recall_at_k
from repro.data.synthetic import clustered_vectors

N, DIM, N_PLANT = 2500, 64, 24


@dataclasses.dataclass
class Planted:
    cfg: object
    data: np.ndarray
    new_vecs: np.ndarray
    queries: np.ndarray       # held out, and last the rows' mean
    raw: clustering.PostingLists   # the assignment before the drop
    index: FusionANNSIndex


@pytest.fixture(scope="module")
def planted():
    """``N_PLANT`` centroids near the rows' mean, between the clusters,
    where they win no row; ``N_PLANT`` is more than ``top_m``, so they
    fill the mean's ``top_m`` if kept."""
    rng = np.random.default_rng(0)
    everything = clustered_vectors(rng, N + 60, DIM, n_clusters=10)
    data, new_vecs = everything[:N], everything[N:N + 20]
    queries = np.concatenate([everything[N + 20:], data.mean(0)[None]])
    cfg = dataclasses.replace(SIFT_SMALL, n_vectors=N, dim=DIM,
                              n_posting_fraction=0.04)
    assert N_PLANT > cfg.top_m
    plant = planting(N_PLANT)
    mp = pytest.MonkeyPatch()
    mp.setattr(clustering, "hierarchical_balanced_clustering", plant)
    try:
        index = FusionANNSIndex.build(data, cfg)
    finally:
        mp.undo()
    raw = clustering.assign_with_replication(
        data, plant.centroids, eps=cfg.replication_eps,
        max_replicas=cfg.max_replicas)
    return Planted(cfg, data, new_vecs, queries, raw, index)


def test_the_plant_would_fill_the_means_top_m(planted):
    sizes = np.array([len(m) for m in planted.raw.members])
    d2 = np.sum((planted.raw.centroids - planted.queries[-1]) ** 2, -1)
    assert (sizes[np.argsort(d2)[:planted.cfg.top_m]] == 0).all()


def test_build_keeps_only_lists_with_members(planted):
    pl = planted.index.posting
    sizes = np.array([len(m) for m in planted.raw.members])
    assert pl.n_clusters == int((sizes > 0).sum()) \
        == len(planted.index.graph.points)
    assert all(len(m) for m in pl.members)
    for i in range(N):
        assert i in pl.members[pl.primary[i]]
    assert planted.index.ssd.layout.n_pages > 0


@pytest.mark.parametrize("path", ["query", "batch", "fused"])
def test_every_query_gets_k_distinct_ids(planted, path):
    ix, k = planted.index, planted.cfg.top_k
    if path == "query":
        res = [ix.query(q) for q in planted.queries]
    elif path == "batch":
        res = ix.batch_query(planted.queries)
    else:
        res = ix.query_batch_fused(planted.queries)
    for r in res:
        assert len(np.unique(r.ids)) == len(r.ids) == k
    for r in res:
        assert r.stats.lists_empty == 0
        assert r.stats.candidates_collected >= k
    gt = ground_truth(planted.data, planted.queries, k)
    assert recall_at_k(np.stack([r.ids for r in res]), gt, k) >= 0.9


def test_seal_keeps_centroid_ids_and_list_prefixes(planted):
    ix = copy.deepcopy(planted.index)
    before = ix.posting
    new_ids = ix.insert(planted.new_vecs)
    assert ix.compact() == len(planted.new_vecs)
    after = ix.posting
    np.testing.assert_array_equal(after.centroids, before.centroids)
    assert after.n_clusters == before.n_clusters
    for old, new in zip(before.members, after.members):
        np.testing.assert_array_equal(new[:len(old)], old)
    np.testing.assert_array_equal(after.primary[:N], before.primary)
    hits = sum(int(ix.query(v, k=1).ids[0] == i)
               for v, i in zip(planted.new_vecs, new_ids))
    assert hits >= 18


def test_snapshot_answers_bit_identically(planted, tmp_path):
    planted.index.save_snapshot(str(tmp_path / "snap"))
    restored = FusionANNSIndex.load_snapshot(str(tmp_path / "snap"))
    assert restored.posting.n_clusters == planted.index.posting.n_clusters
    for q in planted.queries:
        a, b = planted.index.query(q), restored.query(q)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.dists, b.dists)

"""Posting-list construction invariants (paper §4.1, Eq. 2)."""

import numpy as np
import pytest
from _plant import planting
from _propshim import given, settings, strategies as st

from repro.core import clustering
from repro.data.synthetic import clustered_vectors


@pytest.fixture(scope="module")
def posting():
    rng = np.random.default_rng(0)
    data = clustered_vectors(rng, 2000, 16, n_clusters=20)
    return data, clustering.build_posting_lists(
        rng, data, n_clusters=24, eps=0.15, max_replicas=8)


def test_every_vector_assigned(posting):
    data, pl = posting
    seen = np.zeros(len(data), bool)
    for m in pl.members:
        seen[m] = True
    assert seen.all()


def test_primary_is_nearest_centroid(posting):
    data, pl = posting
    d2 = (np.sum(data ** 2, -1)[:, None] - 2 * data @ pl.centroids.T
          + np.sum(pl.centroids ** 2, -1)[None])
    np.testing.assert_array_equal(pl.primary, np.argmin(d2, -1))


def test_replication_cap(posting):
    data, pl = posting
    counts = np.zeros(len(data), np.int64)
    for m in pl.members:
        counts[m] += 1
    assert counts.max() <= 8
    assert counts.min() >= 1
    # replication factor in a sane band (paper reports up to 8x)
    assert 1.0 <= pl.replication_factor() <= 8.0


def test_eq2_epsilon_closure(posting):
    """v in C_i  iff  Dist(v,C_i) <= (1+eps) Dist(v,C_1) (within top-8)."""
    data, pl = posting
    eps = 0.15
    d = np.sqrt(np.maximum(
        np.sum(data ** 2, -1)[:, None] - 2 * data @ pl.centroids.T
        + np.sum(pl.centroids ** 2, -1)[None], 0))
    member_of = [set(m.tolist()) for m in pl.members]
    for v in range(0, len(data), 97):
        d1 = d[v].min()
        within = np.where(d[v] <= (1 + eps) * d1 + 1e-6)[0]
        assigned = {c for c in range(pl.n_clusters) if v in member_of[c]}
        # assigned set == top-(<=8) of the within set
        expect = set(within[np.argsort(d[v][within])][:8].tolist())
        assert assigned == expect


@settings(max_examples=10, deadline=None)
@given(n=st.integers(50, 300), k=st.integers(2, 12),
       seed=st.integers(0, 999))
def test_balanced_clustering_properties(n, k, seed):
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((n, 8)).astype(np.float32)
    cents = clustering.hierarchical_balanced_clustering(rng, data, k)
    assert cents.shape == (k, 8)
    assert np.isfinite(cents).all()


# ---------------------------------------------------------------- empty lists

@pytest.mark.parametrize("far", [True, False])
def test_build_drops_empty_lists_and_renumbers(monkeypatch, far):
    rng = np.random.default_rng(0)
    data = clustered_vectors(rng, 2000, 64, n_clusters=20)
    planted = planting(6, far)
    monkeypatch.setattr(clustering, "hierarchical_balanced_clustering",
                        planted)
    pl = clustering.build_posting_lists(np.random.default_rng(0), data,
                                        n_clusters=60, eps=0.15)
    raw = clustering.assign_with_replication(data, planted.centroids,
                                             eps=0.15)
    raw_sizes = np.array([len(m) for m in raw.members])
    assert (raw_sizes == 0).sum() >= 6            # the plant took
    kept = np.flatnonzero(raw_sizes > 0)
    assert pl.n_clusters == len(kept)
    assert all(len(m) for m in pl.members)
    np.testing.assert_array_equal(pl.centroids, planted.centroids[kept])
    for new, old in enumerate(kept):               # members, renumbered
        np.testing.assert_array_equal(pl.members[new], raw.members[old])
    np.testing.assert_array_equal(kept[pl.primary], raw.primary)
    for i in range(len(data)):
        assert i in pl.members[pl.primary[i]]


def test_a_row_that_is_its_own_centroid_joins_its_list():
    """A cluster of one: its squared distance reads a little below 0 after
    cancellation, and Eq. 2 must still keep the row in its own list."""
    rng = np.random.default_rng(3)
    data = (10.0 * rng.standard_normal((600, 32))).astype(np.float32)
    pl = clustering.assign_with_replication(data, data[:40].copy())
    for i in range(40):
        assert i in pl.members[pl.primary[i]]
        assert pl.primary[i] == i

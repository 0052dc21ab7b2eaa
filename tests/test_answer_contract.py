"""The answer contract the benchmark holds the chip to (``bench/check.py``),
checked on the serving path at the shapes the chip serves: answers that
come back through ``make_serving_stack`` on either scan path carry k
distinct in-range ids, ascending finite distances that are the exact
squared L2 of their ids, and Recall@10 >= 0.9 against exact ground truth.
"""

import numpy as np
import pytest

from repro.core.engine import recall_at_k
from repro.serve.client import ANNSClient, SearchRequest
from repro.serve.stack import make_serving_stack

K = 10


@pytest.fixture(scope="module", params=[False, True],
                ids=["dense", "fused"])
def served(bench_shape, request):
    """Every held-out query of ``bench_shape`` answered by a two-replica
    threaded stack on the dense or the fused scan path."""
    b = bench_shape
    router = make_serving_stack(b.index, fused=request.param)
    try:
        answers = ANNSClient(router).search_many(
            [SearchRequest(query=q, k=K) for q in b.queries], timeout=120)
    finally:
        router.stop()
    return b, answers


def test_k_distinct_in_range_ids(served):
    b, answers = served
    for a in answers:
        assert a.ids.shape == (K,)
        assert len(set(a.ids.tolist())) == K
        assert a.ids.min() >= 0 and a.ids.max() < len(b.data)


def test_distances_ascend_and_are_finite(served):
    _, answers = served
    for a in answers:
        assert np.all(np.isfinite(a.dists))
        assert np.all(np.diff(a.dists) >= 0)


def test_distances_are_the_exact_squared_l2_of_their_ids(served):
    b, answers = served
    for q, a in zip(b.queries, answers):
        diff = b.data[a.ids].astype(np.float64) - q.astype(np.float64)
        exact = np.sum(diff * diff, axis=1)
        np.testing.assert_allclose(a.dists, exact, rtol=1e-3)


def test_recall_at_10_meets_the_bar(served):
    b, answers = served
    got = np.stack([a.ids for a in answers])
    assert recall_at_k(got, b.gt, K) >= 0.9

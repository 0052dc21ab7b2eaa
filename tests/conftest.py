"""Shared fixtures.

Policy notes
------------
* ``hypothesis`` is OPTIONAL: property tests import ``given``/``settings``/
  ``strategies`` from ``tests/_propshim.py``, which uses the real package
  when installed and otherwise falls back to a small deterministic
  generator covering the strategy subset this suite uses.  Tier-1 must
  collect and pass with no ``hypothesis`` in the environment.
* One small FusionANNS index is built ONCE per session (``anns_bundle``)
  and shared by the engine / system / executor / service / updates
  modules; tests that mutate the index (insert/delete) take the
  ``fresh_index`` deep copy instead of rebuilding.
* Heavy system tests carry ``@pytest.mark.slow`` and are deselected by
  default via pytest.ini; run them with ``-m ""`` or
  ``scripts/check.sh full``.
"""

import dataclasses
import os
import sys

# smoke tests / benches must see exactly 1 device (dryrun.py alone forces
# 512); keep any user XLA_FLAGS out of the test environment.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))      # for _propshim, _plant

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _witness_guard():
    """When LINT_LOCKS is set, the serving stack's locks are OrderedLock
    witnesses recording nested acquisitions.  Fail any test whose body
    produced a hierarchy inversion (record mode collects instead of
    raising so the offending test — not a later one — gets the blame)."""
    from repro.analysis.concurrency import witness
    if not witness.enabled():
        yield
        return
    witness.WITNESS.drain_violations()
    yield
    bad = witness.WITNESS.drain_violations()
    assert not bad, ("lock-order violations witnessed:\n"
                     + "\n".join(map(str, bad)))


@dataclasses.dataclass
class ANNSBundle:
    """One built index + held-out data shared across test modules."""

    cfg: object
    data: np.ndarray          # the indexed vectors
    new_vecs: np.ndarray      # held-out rows for insert tests (never indexed)
    queries: np.ndarray       # held-out query rows
    gt: np.ndarray            # exact top-10 ids for ``queries`` over ``data``
    index: object


@pytest.fixture(scope="session")
def anns_bundle() -> ANNSBundle:
    from repro.configs.anns_datasets import SIFT_SMALL
    from repro.core.engine import FusionANNSIndex, ground_truth
    from repro.data.synthetic import clustered_vectors

    rng = np.random.default_rng(0)
    n, dim = 2500, 32
    cfg = dataclasses.replace(SIFT_SMALL, n_vectors=n, dim=dim,
                              n_posting_fraction=0.02)
    everything = clustered_vectors(rng, n + 40, dim, n_clusters=24)
    data, new_vecs, queries = (everything[:n], everything[n:n + 20],
                               everything[n + 20:])
    index = FusionANNSIndex.build(data, cfg)
    gt = ground_truth(data, queries, 10)
    return ANNSBundle(cfg=cfg, data=data, new_vecs=new_vecs,
                      queries=queries, gt=gt, index=index)


@pytest.fixture
def fresh_index(anns_bundle):
    """Mutable deep copy of the shared index (for insert/delete tests)."""
    import copy
    return copy.deepcopy(anns_bundle.index)

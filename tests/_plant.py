"""A clustering that leaves empty posting lists, for the tests of the
build that drops them."""

import numpy as np

from repro.core import clustering


def planting(n_plant: int, far: bool = False):
    """``hierarchical_balanced_clustering`` with ``n_plant`` of its
    centroids planted among its own, every other one from the front: near
    the rows' mean, between the clusters, or ``far`` from every row;
    either way they win no row in the assignment.  The function it
    returns keeps the centroids it gave last in ``.centroids``."""
    real = clustering.hierarchical_balanced_clustering

    def planted(rng, data, n_clusters, **kw):
        cents = real(rng, data, n_clusters - n_plant, **kw)
        at = (data.max(0) + 100.0 * np.abs(data).max() if far
              else data.mean(0))
        jitter = np.random.default_rng(1).standard_normal(
            (n_plant, data.shape[1]))
        out = list(cents)
        for i, e in enumerate((at + 1e-3 * jitter).astype(np.float32)):
            out.insert(2 * i, e)
        planted.centroids = np.stack(out)
        return planted.centroids
    return planted

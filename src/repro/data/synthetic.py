"""Synthetic data generators (host-side numpy; deterministic by seed).

``clustered_vectors`` draws from a Gaussian-mixture so IVF clustering and the
paper's "re-rank candidates are spatially close" locality claim (§4.3) are
actually exercised rather than vacuous, as they would be on iid uniform data.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def clustered_vectors(rng: np.random.Generator, n: int, dim: int,
                      n_clusters: Optional[int] = None,
                      spread: float = 0.15,
                      dtype=np.float32) -> np.ndarray:
    n_clusters = n_clusters or max(8, n // 500)
    centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    assign = rng.integers(0, n_clusters, n)
    x = centers[assign] + spread * rng.standard_normal((n, dim)).astype(
        np.float32)
    if np.issubdtype(dtype, np.integer):
        lo = np.iinfo(dtype).min
        hi = np.iinfo(dtype).max
        x = np.clip(np.round(128 * x), lo, hi)
    return x.astype(dtype)


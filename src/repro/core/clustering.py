"""Posting-list construction (paper §4.1): hierarchical balanced clustering
+ the ε-replication closure of Eq. (2) with the ≤8-replica cap.

The splits of the hierarchy run in numpy on the host; the two passes that
touch every vector against every centroid (the global Lloyd polish and the
replicated assignment) run on the default device in row blocks."""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PostingLists:
    centroids: np.ndarray            # (C, D) f32
    members: List[np.ndarray]        # per-cluster vector-ids (with replicas)
    primary: np.ndarray              # (N,) nearest-cluster id per vector

    @property
    def n_clusters(self) -> int:
        return len(self.members)

    def replication_factor(self) -> float:
        total = sum(len(m) for m in self.members)
        return total / max(len(self.primary), 1)


def _kmeans(rng: np.random.Generator, data: np.ndarray, k: int,
            iters: int = 10, chunk: int = 65536) -> np.ndarray:
    """Plain Lloyd k-means (numpy, chunked distance) — the leaf step of the
    hierarchical balanced clustering."""
    n = len(data)
    centers = data[rng.choice(n, size=k, replace=n < k)].astype(np.float32)
    for _ in range(iters):
        assign = np.empty(n, np.int32)
        for s in range(0, n, chunk):
            blk = data[s:s + chunk]
            d2 = (np.sum(blk ** 2, -1)[:, None]
                  - 2.0 * blk @ centers.T + np.sum(centers ** 2, -1)[None])
            assign[s:s + chunk] = np.argmin(d2, -1)
        for c in range(k):
            pts = data[assign == c]
            if len(pts):
                centers[c] = pts.mean(0)
    return centers


def hierarchical_balanced_clustering(
        rng: np.random.Generator, data: np.ndarray, n_clusters: int,
        branch: int = 8, max_leaf: Optional[int] = None) -> np.ndarray:
    """Recursively k-means-split the largest partition until ``n_clusters``
    leaves exist (keeps leaves balanced — the paper's [34] lineage).
    Returns centroids (n_clusters, D)."""
    parts: List[np.ndarray] = [np.arange(len(data))]
    while len(parts) < n_clusters:
        parts.sort(key=len)
        big = parts.pop()                      # split the largest
        k = min(branch, max(2, n_clusters - len(parts)))
        if len(big) <= k:
            parts.append(big)
            break
        centers = _kmeans(rng, data[big], k, iters=6)
        d2 = (np.sum(data[big] ** 2, -1)[:, None]
              - 2.0 * data[big] @ centers.T
              + np.sum(centers ** 2, -1)[None])
        assign = np.argmin(d2, -1)
        new = [big[assign == c] for c in range(k)]
        parts.extend(p for p in new if len(p))
    cents = np.stack([data[p].mean(0) if len(p) else data[0]
                      for p in parts[:n_clusters]]).astype(np.float32)
    # polish with a few global Lloyd rounds
    return _kmeans_polish(data, cents, iters=4)


# rows per device call: bounds the (rows, C) distance block in device
# memory (16k x 20k centroids = 1.3 GB of f32)
_ROWS = 16384


@functools.partial(jax.jit, static_argnames=("r",))
def _nearest(blk: jax.Array, centers: jax.Array, r: int):
    """The ``r`` nearest centroids of each row: (squared distances, ids),
    both (rows, r), ascending.  Full f32 matmul precision: the squared
    distances subtract terms ~|x|^2 apart, which bf16 passes would swamp.
    They are clamped at 0: a row that is its own centroid (a cluster of
    one) reads a little below 0 after the subtraction, and Eq. 2's
    ``d <= (1+eps)^2 * d_1`` then kept it out of every list."""
    d2 = (jnp.sum(blk * blk, -1)[:, None]
          - 2.0 * jnp.dot(blk, centers.T, precision=jax.lax.Precision.HIGHEST)
          + jnp.sum(centers * centers, -1)[None])
    d2 = jnp.maximum(d2, 0.0)
    neg, idx = jax.lax.top_k(-d2, r)
    return -neg, idx


@jax.jit
def _polish_sums(blk: jax.Array, valid: jax.Array, centers: jax.Array):
    """One Lloyd step's per-centroid (sums, counts) over a row block."""
    _, idx = _nearest(blk, centers, 1)
    w = valid.astype(jnp.float32)
    c = centers.shape[0]
    return (jax.ops.segment_sum(blk * w[:, None], idx[:, 0], num_segments=c),
            jax.ops.segment_sum(w, idx[:, 0], num_segments=c))


def _row_blocks(data: np.ndarray):
    """``(start, block, n_real)`` over ``data`` in f32 blocks of at most
    ``_ROWS`` rows, zero-padded to a power of two (>= 256) so a build
    compiles a handful of programs, not one per delta size."""
    for s in range(0, len(data), _ROWS):
        blk = np.asarray(data[s:s + _ROWS], np.float32)
        n = len(blk)
        size = max(256, 1 << (n - 1).bit_length())
        if size > n:
            blk = np.concatenate(
                [blk, np.zeros((size - n, blk.shape[1]), np.float32)])
        yield s, blk, n


def _kmeans_polish(data: np.ndarray, centers: np.ndarray,
                   iters: int = 4) -> np.ndarray:
    """Global Lloyd rounds with the N x C assignment on the device."""
    for _ in range(iters):
        c_dev = jnp.asarray(centers)
        sums = jnp.zeros(centers.shape, jnp.float32)
        cnts = jnp.zeros(len(centers), jnp.float32)
        for _, blk, n in _row_blocks(data):
            ds, dc = _polish_sums(blk, np.arange(len(blk)) < n, c_dev)
            sums, cnts = sums + ds, cnts + dc
        sums, cnts = np.asarray(sums), np.asarray(cnts)
        nz = cnts > 0
        centers[nz] = sums[nz] / cnts[nz, None]
    return centers


def assign_with_replication(data: np.ndarray, centroids: np.ndarray,
                            eps: float = 0.10,
                            max_replicas: int = 8) -> PostingLists:
    """Eq. (2): v ∈ C_i  ⇔  Dist(v, C_i) ≤ (1+ε)·Dist(v, C_1), capped at
    ``max_replicas`` clusters per vector."""
    n = len(data)
    c = len(centroids)
    r = min(max_replicas, c)
    c_dev = jnp.asarray(centroids, jnp.float32)
    primary = np.empty(n, np.int32)
    vids, cids = [], []
    for s, blk, n_real in _row_blocks(data):
        dd, idx = _nearest(blk, c_dev, r)
        dd, idx = np.asarray(dd)[:n_real], np.asarray(idx)[:n_real]
        primary[s:s + n_real] = idx[:, 0]
        # Eq. 2 threshold on *distances* (squared dist => (1+eps)^2)
        row, j = np.nonzero(dd <= (1.0 + eps) ** 2 * dd[:, :1])
        vids.append(s + row)
        cids.append(idx[row, j])
    vids = np.concatenate(vids) if vids else np.zeros(0, np.int64)
    cids = np.concatenate(cids) if cids else np.zeros(0, np.int64)
    # group by cluster; the stable sort keeps each list in ascending id order
    order = np.argsort(cids, kind="stable")
    bounds = np.cumsum(np.bincount(cids, minlength=c))[:-1]
    return PostingLists(
        centroids=centroids.astype(np.float32),
        members=[m.astype(np.int32)
                 for m in np.split(vids[order], bounds)],
        primary=primary)


def build_posting_lists(rng: np.random.Generator, data: np.ndarray,
                        n_clusters: int, eps: float = 0.10,
                        max_replicas: int = 8) -> PostingLists:
    """Clusters ``data`` and assigns it with replication, then drops every
    centroid whose list came out empty, so no query's ``top_m`` holds a
    list with nothing in it.  The kept lists are renumbered densely in
    their old order, and ``primary`` with them; a vector's primary list
    always holds it, so no primary is dropped."""
    cents = hierarchical_balanced_clustering(rng, data, n_clusters)
    pl = assign_with_replication(data, cents, eps, max_replicas)
    keep = np.array([len(m) > 0 for m in pl.members])
    new_id = np.cumsum(keep, dtype=np.int32) - 1
    return PostingLists(
        centroids=pl.centroids[keep],
        members=[m for m, k in zip(pl.members, keep) if k],
        primary=new_id[pl.primary])

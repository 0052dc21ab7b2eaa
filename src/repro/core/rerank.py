"""Heuristic re-ranking — paper §4.2, Algorithm 1.

Host version (numpy): the production placement — the CPU re-ranks using raw
vectors read from the SSD tier (``core.io_sim``): top-k by exact squared
L2, change rate Δ = |S_n − S_n∩S_{n−1}|/k (Eq. 3), early termination after
β stable mini-batches.  It runs in lockstep over a block of queries (the
landed scan window): step b scores mini-batch b of every query still
active with one gather and one distance reduction, and merges the scores
into a (B, k) top-k.  Δ and the β count are kept per query, and a query
drops out when it stops early or runs out of candidates, so each scores
exactly the mini-batches it would alone and reads no raw vector past its
early stop.  One query is a block of one.  The top-k is ordered by
(distance, id), the scan merge's rule, so at exactly equal float32
distance the smaller id is kept.  Page I/O is counted after the loop from
each query's mini-batch count (``SSDSim.account``): no decision of
Algorithm 1 depends on it.

Device version (``lax.while_loop``): same control flow with a fixed-size
top-k buffer, for TPU-resident re-ranking when raw vectors live in HBM
(beyond-paper mode used by the distributed engine).
"""

from __future__ import annotations

import dataclasses
from typing import List, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.io_sim import IOStats, SSDSim

# A scored candidate is one int64 key, its float32 distance's bits (which
# order as the distances do, for d >= 0) above its row (< 2**32 - 2), so
# one sort orders by (distance, row).  An empty top-k slot sorts after any
# scored row and before a ragged mini-batch's pad, so a pad is never kept.
_PAD_ROW = 0xFFFFFFFF
_EMPTY = (0x7F800000 << 32) | (_PAD_ROW - 1)          # inf, an empty slot


@dataclasses.dataclass
class RerankResult:
    """One query: ``ids``/``dists`` (n,) in ascending (distance, id) order,
    n = min(k, candidates scored), scalar counters and one ``IOStats``.
    A block of B queries: ``ids``/``dists`` (B, k), padded past each
    row's n with id -1 and distance inf, each counter a (B,) array and
    ``io`` a list of B ``IOStats``; ``row(i)`` is query i's own result."""

    ids: np.ndarray
    dists: np.ndarray
    batches_run: Union[int, np.ndarray]
    candidates_scored: Union[int, np.ndarray]
    io: Union[IOStats, List[IOStats]]
    early_stopped: Union[bool, np.ndarray]

    def row(self, i: int) -> "RerankResult":
        n = min(self.ids.shape[1], int(self.candidates_scored[i]))
        return RerankResult(ids=self.ids[i, :n], dists=self.dists[i, :n],
                            batches_run=int(self.batches_run[i]),
                            candidates_scored=int(self.candidates_scored[i]),
                            io=self.io[i],
                            early_stopped=bool(self.early_stopped[i]))


def heuristic_rerank(query: np.ndarray, candidate_ids, ssd: SSDSim, k: int,
                     *, batch_size: int = 32, eps: float = 0.05,
                     beta: int = 2,
                     disable_early_stop: bool = False) -> RerankResult:
    """Algorithm 1 for one query ``(D,)`` with its candidate rows, or in
    lockstep for a block ``(B, D)`` with a sequence of B candidate-row
    arrays.  Each query's rows must be sorted by ascending PQ distance
    (the scan's output order — step ⑦) and hold no repeats."""
    if np.ndim(query) == 1:
        return heuristic_rerank(
            np.asarray(query)[None], [candidate_ids], ssd, k,
            batch_size=batch_size, eps=eps, beta=beta,
            disable_early_stop=disable_early_stop).row(0)
    qs = np.asarray(query, np.float32)
    cands: List[np.ndarray] = [np.asarray(c, np.int64)
                               for c in candidate_ids]
    lens = np.array([len(c) for c in cands], np.int64)
    n_mb = -(-lens // batch_size)
    steps = int(n_mb.max(initial=0))
    # steps at which some query's last mini-batch is short
    ragged = set((n_mb[lens % batch_size > 0] - 1).tolist())
    batches = np.zeros(len(cands), np.int64)
    early = np.zeros(len(cands), bool)
    top = np.full((len(cands), k), _EMPTY, np.int64)
    # Δ < eps  <=>  fewer than `few` new top-k members (Δ rises with them)
    few = sum(f / max(k, 1) < eps for f in range(k + 1))
    # the queries still active, compacted; each row of `work` holds its
    # query's top-k keys in [:k] and the mini-batch being merged in [k:]
    live = np.flatnonzero(n_mb)
    q, left = qs[live], n_mb[live]
    rows = np.full((len(live), steps * batch_size), -1, np.int64)
    for j, i in enumerate(live):
        rows[j, :lens[i]] = cands[i]
    work = np.full((len(live), k + batch_size), _EMPTY, np.int64)
    stable = np.zeros(len(live), np.int64)
    for b in range(steps):
        ids = rows[:, b * batch_size:(b + 1) * batch_size]
        # row-wise float32 squared L2, the same reduction per row as
        # scoring one query's mini-batch alone
        if b not in ragged:
            x = ssd.vectors[ids].astype(np.float32, copy=False) - q[:, None]
            x *= x
            d = np.add.reduce(x, -1)
        else:
            real = ids >= 0
            x = (ssd.vectors[ids[real]].astype(np.float32, copy=False)
                 - np.repeat(q, np.add.reduce(real, 1), 0))
            x *= x
            d = np.full(ids.shape, np.inf, np.float32)
            d[real] = np.add.reduce(x, -1)
            ids = np.where(real, ids, _PAD_ROW)
        new = (d.view(np.int32).astype(np.int64) << 32) | ids
        work[:, k:] = new
        work.sort(1)
        done = left == b + 1
        if not disable_early_stop:
            # Δ·k (Eq. 3): the mini-batch's members of the new top-k
            calm = np.add.reduce(new < work[:, k:k + 1], 1) < few
            stable += 1
            stable *= calm
            stop = stable >= beta
            stop &= calm
            done |= stop
        if done.any():
            out = live[done]
            top[out] = work[done, :k]
            batches[out] = b + 1
            if not disable_early_stop:
                early[out] = stop[done]
            go = ~done
            if not go.any():
                break
            live, q, left, rows, work, stable = (
                live[go], q[go], left[go], rows[go], work[go], stable[go])
    row = top & 0xFFFFFFFF
    return RerankResult(
        ids=np.where(row >= _PAD_ROW - 1, -1, row).astype(np.int32),
        dists=(top >> 32).astype(np.int32).view(np.float32),
        batches_run=batches,
        candidates_scored=np.minimum(lens, batches * batch_size),
        io=ssd.account(cands, batches, batch_size), early_stopped=early)


def heuristic_rerank_jax(query: jax.Array, cand_vectors: jax.Array,
                         cand_ids: jax.Array, k: int, *,
                         batch_size: int = 32, eps: float = 0.05,
                         beta: int = 2):
    """Device-side Algorithm 1 over HBM-resident candidates.

    cand_vectors (n, D) sorted by PQ distance; returns (ids (k,), dists (k,),
    batches_run).  Distances of unprocessed batches never affect the heap —
    the while_loop stops exactly like the host version.

    The tail batch (``n % batch_size`` candidates) is scored too: inputs
    are padded to a whole number of batches and the pad rows carry +inf
    distance / id -1, so they can never displace a real candidate."""
    n, d = cand_vectors.shape
    n_batches = -(-n // batch_size)           # ceil: include the tail batch
    pad = n_batches * batch_size - n
    if pad:
        cand_vectors = jnp.concatenate(
            [cand_vectors, jnp.zeros((pad, d), cand_vectors.dtype)], axis=0)
        cand_ids = jnp.concatenate(
            [cand_ids, jnp.full((pad,), -1, cand_ids.dtype)], axis=0)
    q = query.astype(jnp.float32)

    top_d0 = jnp.full((k,), jnp.inf, jnp.float32)
    top_i0 = jnp.full((k,), -1, jnp.int32)

    def body(state):
        b, top_d, top_i, stab, done = state
        start = b * batch_size
        vecs = jax.lax.dynamic_slice_in_dim(cand_vectors, start, batch_size)
        ids = jax.lax.dynamic_slice_in_dim(cand_ids, start, batch_size)
        dist = jnp.sum((vecs.astype(jnp.float32) - q[None]) ** 2, axis=1)
        valid = start + jnp.arange(batch_size) < n
        dist = jnp.where(valid, dist, jnp.inf)    # mask tail padding
        all_d = jnp.concatenate([top_d, dist])
        all_i = jnp.concatenate([top_i, ids.astype(jnp.int32)])
        neg, pos = jax.lax.top_k(-all_d, k)
        new_d, new_i = -neg, all_i[pos]
        # Δ = fraction of heap slots replaced this batch (Eq. 3)
        changed = jnp.sum(~jnp.isin(new_i, top_i)) / k
        stab = jnp.where(changed < eps, stab + 1, 0)
        done = stab >= beta
        return b + 1, new_d, new_i, stab, done

    def cond(state):
        b, _, _, _, done = state
        return jnp.logical_and(b < n_batches, ~done)

    b, top_d, top_i, stab, done = jax.lax.while_loop(
        cond, body, (0, top_d0, top_i0, 0, False))
    return top_i, top_d, b

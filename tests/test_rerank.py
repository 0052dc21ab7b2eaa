"""Heuristic re-ranking (Algorithm 1) invariants."""

import jax.numpy as jnp
import numpy as np
import pytest
from _propshim import given, settings, strategies as st

from repro.core.io_sim import SSDSim, StorageLayout
from repro.core.rerank import heuristic_rerank, heuristic_rerank_jax


def _setup(rng, n=200, d=16):
    data = rng.standard_normal((n, d)).astype(np.float32)
    primary = rng.integers(0, 8, n).astype(np.int64)
    lay = StorageLayout.build(primary, 8, 4 * d)
    return data, SSDSim(data, lay)


def test_full_rerank_equals_bruteforce(rng):
    data, ssd = _setup(rng)
    q = rng.standard_normal(16).astype(np.float32)
    cand = np.arange(200)
    rr = heuristic_rerank(q, cand, ssd, k=10, batch_size=32,
                          disable_early_stop=True)
    exact = np.argsort(np.sum((data - q) ** 2, -1))[:10]
    np.testing.assert_array_equal(np.sort(rr.ids), np.sort(exact))
    assert rr.batches_run == 200 // 32 + 1


def test_dists_ascending(rng):
    data, ssd = _setup(rng)
    q = rng.standard_normal(16).astype(np.float32)
    rr = heuristic_rerank(q, np.arange(100), ssd, k=10)
    assert (np.diff(rr.dists) >= -1e-6).all()


def test_early_stop_reduces_work(rng):
    data, ssd = _setup(rng)
    q = data[0] + 0.01 * rng.standard_normal(16).astype(np.float32)
    # candidates sorted by true distance => heap stabilises fast
    order = np.argsort(np.sum((data - q) ** 2, -1))
    rr_es = heuristic_rerank(q, order, ssd, k=10, batch_size=16,
                             eps=0.05, beta=2)
    rr_full = heuristic_rerank(q, order, ssd, k=10, batch_size=16,
                               disable_early_stop=True)
    assert rr_es.batches_run < rr_full.batches_run
    assert rr_es.early_stopped
    # early stop on sorted candidates must not hurt the result here
    np.testing.assert_array_equal(rr_es.ids, rr_full.ids)


def test_beta_delays_termination(rng):
    data, ssd = _setup(rng)
    q = rng.standard_normal(16).astype(np.float32)
    order = np.argsort(np.sum((data - q) ** 2, -1))
    b1 = heuristic_rerank(q, order, ssd, k=10, batch_size=16, beta=1)
    b3 = heuristic_rerank(q, order, ssd, k=10, batch_size=16, beta=3)
    assert b1.batches_run <= b3.batches_run


def test_jax_version_matches_host(rng):
    data, ssd = _setup(rng, n=128)
    q = rng.standard_normal(16).astype(np.float32)
    order = np.argsort(np.sum((data - q) ** 2, -1)).astype(np.int32)
    host = heuristic_rerank(q, order, ssd, k=8, batch_size=16, eps=0.05,
                            beta=2)
    ids, dists, batches = heuristic_rerank_jax(
        jnp.asarray(q), jnp.asarray(data[order]), jnp.asarray(order), 8,
        batch_size=16, eps=0.05, beta=2)
    assert int(batches) == host.batches_run
    np.testing.assert_array_equal(np.sort(np.asarray(ids)),
                                  np.sort(host.ids))


def test_jax_tail_batch_scored(rng):
    """Satellite regression: n % batch_size != 0 — the device version
    silently never scored the last partial batch.  Best candidates are
    placed IN the tail, so missing it provably corrupts the result."""
    data, ssd = _setup(rng, n=100)
    q = rng.standard_normal(16).astype(np.float32)
    # descending true distance: the k best candidates live at the end,
    # with the 4 very best inside the ragged tail batch
    order = np.argsort(np.sum((data - q) ** 2, -1))[::-1].astype(np.int32)
    ids, dists, batches = heuristic_rerank_jax(
        jnp.asarray(q), jnp.asarray(data[order]), jnp.asarray(order), 8,
        batch_size=16, eps=0.0, beta=2)       # eps=0: no early stop
    assert int(batches) == -(-100 // 16)      # ceil: the tail batch ran
    exact = np.argsort(np.sum((data - q) ** 2, -1))[:8]
    np.testing.assert_array_equal(np.sort(np.asarray(ids)), np.sort(exact))


def test_jax_matches_host_ragged(rng):
    """Host-vs-device parity (batch count + ids) with a ragged tail and
    early stopping enabled."""
    data, ssd = _setup(rng, n=120)
    q = rng.standard_normal(16).astype(np.float32)
    cand = rng.permutation(120).astype(np.int32)
    host = heuristic_rerank(q, cand, ssd, k=8, batch_size=32, eps=0.05,
                            beta=2)
    ids, dists, batches = heuristic_rerank_jax(
        jnp.asarray(q), jnp.asarray(data[cand]), jnp.asarray(cand), 8,
        batch_size=32, eps=0.05, beta=2)
    assert int(batches) == host.batches_run
    np.testing.assert_array_equal(np.sort(np.asarray(ids)),
                                  np.sort(host.ids))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 999), k=st.integers(1, 20),
       batch=st.sampled_from([8, 16, 32]))
def test_topk_is_prefix_optimal(seed, k, batch):
    """Whatever prefix Alg. 1 scans, its output is the exact top-k of that
    prefix (the heap never loses a better candidate)."""
    rng = np.random.default_rng(seed)
    data, ssd = _setup(rng, n=160)
    q = rng.standard_normal(16).astype(np.float32)
    cand = rng.permutation(160)
    rr = heuristic_rerank(q, cand, ssd, k=k, batch_size=batch)
    scanned = cand[:rr.batches_run * batch]
    d = np.sum((data[scanned] - q) ** 2, -1)
    expect = scanned[np.argsort(d)[:k]]
    np.testing.assert_array_equal(np.sort(rr.ids), np.sort(expect))


# ------------------------------------------------ the per-query reference
# Algorithm 1 one query at a time, with a heap and a page walk per
# mini-batch, as the host re-rank ran before it ran in lockstep: the
# window form must give the same answer and the same counters row by row.

def _ref_fetch(ssd, vec_ids, stats, buf):
    pages = ssd.layout.page_of[vec_ids]
    stats.pages_requested += len(pages)
    wanted = pages if not ssd.intra_merge else np.unique(pages)
    stats.intra_merged += len(pages) - len(wanted)
    read = []
    for p in wanted:
        p = int(p)
        if ssd.use_buffer and buf.hit(p):
            stats.buffer_hits += 1
            continue
        stats.ios += 1
        stats.bytes_read += ssd.layout.page_bytes
        read.append(p)
    if ssd.use_buffer:
        for p in read:
            buf.insert(p)
    return ssd.vectors[vec_ids]


def _ref_rerank(query, candidate_ids, ssd, k, *, batch_size=32, eps=0.05,
                beta=2, disable_early_stop=False):
    import heapq
    from repro.core.io_sim import IOStats, PageBuffer
    q = query.astype(np.float32)
    stats, buf = IOStats(), PageBuffer(ssd.buffer_pages)
    heap, stability, batches, scored, early = [], 0, 0, 0, False
    for start in range(0, len(candidate_ids), batch_size):
        prev = {vid for _, vid in heap}
        batch = candidate_ids[start:start + batch_size]
        vecs = _ref_fetch(ssd, batch, stats, buf)
        d = np.sum((vecs.astype(np.float32) - q[None]) ** 2, axis=1)
        for dist, vid in zip(d, batch):
            scored += 1
            if len(heap) < k:
                heapq.heappush(heap, (-dist, int(vid)))
            elif dist < -heap[0][0]:
                heapq.heapreplace(heap, (-dist, int(vid)))
        batches += 1
        delta = len({vid for _, vid in heap} - prev) / max(k, 1)
        if not disable_early_stop:
            if delta < eps:
                stability += 1
                if stability >= beta:
                    early = True
                    break
            else:
                stability = 0
    order = sorted((-nd, vid) for nd, vid in heap)
    return (np.array([v for _, v in order], np.int32),
            np.array([d for d, _ in order], np.float32),
            batches, scored, early, stats)


IO_FIELDS = ("ios", "pages_requested", "buffer_hits", "intra_merged",
             "bytes_read")


@pytest.mark.parametrize("case", [
    dict(),
    dict(eps=0.2, beta=1),
    dict(eps=0.0, beta=3),
    dict(eps=0.3, beta=0),
    dict(eps=0.1, beta=4, batch_size=16),
    dict(disable_early_stop=True),
    dict(intra_merge=False),
    dict(use_buffer=False),
    dict(intra_merge=False, use_buffer=False),
    dict(buffer_pages=3),                  # the LRU evicts: the replay
    dict(buffer_pages=3, intra_merge=False, batch_size=8),
    dict(k=1, batch_size=8),
    dict(k=40),                            # more than some queries score
    dict(d=96),                            # the served vector widths
    dict(d=128),
], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()) or "default")
def test_window_form_matches_per_query_reference(case):
    """A block of queries against the frozen per-query form, row by row:
    ids, dists, counters and every I/O counter.  Candidate counts of 0,
    under one mini-batch, whole mini-batches and ragged tails."""
    case = dict(case)
    ssd_kw = {key: case.pop(key) for key in ("intra_merge", "use_buffer",
                                             "buffer_pages") if key in case}
    k = case.pop("k", 10)
    d = case.pop("d", 16)
    rng = np.random.default_rng(11)
    n = 400
    data = rng.standard_normal((n, d)).astype(np.float32)
    lay = StorageLayout.build(rng.integers(0, 12, n).astype(np.int64), 12,
                              4 * d)
    ssd = SSDSim(data, lay, **ssd_kw)
    qs = rng.standard_normal((7, d)).astype(np.float32)
    cands = []
    for q, m in zip(qs, [0, 5, 32, 100, 161, 400, 250]):
        # the scan's order: PQ-like distances, the true ones plus noise
        noisy = np.sum((data - q) ** 2, -1) + rng.normal(0, 4.0, n)
        cands.append(np.argsort(noisy)[:m])
    block = heuristic_rerank(qs, cands, ssd, k, **case)
    assert block.ids.shape == block.dists.shape == (len(qs), k)
    for i, (q, c) in enumerate(zip(qs, cands)):
        ids, dists, batches, scored, early, io = _ref_rerank(q, c, ssd, k,
                                                             **case)
        for got in (block.row(i), heuristic_rerank(q, c, ssd, k, **case)):
            np.testing.assert_array_equal(got.ids, ids)
            np.testing.assert_array_equal(got.dists, dists)
            assert (got.batches_run, got.candidates_scored,
                    got.early_stopped) == (batches, scored, early)
            for f in IO_FIELDS:
                assert getattr(got.io, f) == getattr(io, f), f
    assert block.batches_run[0] == 0 and block.ids[0].tolist() == [-1] * k


def test_equal_distances_keep_the_smaller_id():
    """At exactly equal float32 distance the top-k keeps the smaller id
    and lists it first, wherever the two sit in the candidate order."""
    rng = np.random.default_rng(3)
    data = rng.standard_normal((64, 8)).astype(np.float32)
    data[9] = data[40]                      # rows 9 and 40 tie exactly
    lay = StorageLayout.build(np.zeros(64, np.int64), 1, 32)
    ssd = SSDSim(data, lay)
    q = data[40] + 0.5
    far = np.argsort(np.sum((data - q) ** 2, -1))[::-1]
    far = far[(far != 9) & (far != 40)][:30]
    for cand in ([40, 9], [9, 40], [40, *far, 9]):
        one = heuristic_rerank(q, np.array(cand), ssd, k=1, batch_size=4,
                               disable_early_stop=True)
        assert one.ids.tolist() == [9]
        two = heuristic_rerank(q, np.array(cand), ssd, k=2, batch_size=4,
                               disable_early_stop=True)
        assert two.ids.tolist() == [9, 40]
        assert two.dists[0] == two.dists[1]

"""The plain reference: exact k nearest neighbours by squared L2, brute
force over the whole corpus on the device, in blocks.

For each block of queries the device scores every corpus row as
``|x|^2 - 2 q.x`` at HIGHEST precision and keeps a shortlist of the
``k + SHORTLIST_EXTRA`` best; the host then recomputes the shortlist's
distances exactly, as ``sum((q - x)^2)`` in float64, and keeps the ``k``
smallest by (distance, id).  The shortlist's slack covers the float32
rounding of the expanded form, which is far smaller than the gap between
a point's 10th and 42nd neighbour.

``precision="bfloat16"`` is the control: the same search computed in
bfloat16, the nearest precision below the float32 that the configurations
state.  It returns its own bfloat16 distances and does no exact refinement,
as a program that scored in bfloat16 would.

Nothing here imports the program.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

SHORTLIST_EXTRA = 32
_Q_BLOCK = 256
_C_BLOCK = 1 << 16


@functools.partial(jax.jit, static_argnames=("kk", "low"))
def _scan(corpus_blocks, norm_blocks, queries, *, kk: int, low: bool):
    """Best ``kk`` (score, row) per query over (n_blocks, C, D) blocks."""
    dt = jnp.bfloat16 if low else jnp.float32
    prec = jax.lax.Precision.DEFAULT if low else jax.lax.Precision.HIGHEST
    q = queries.astype(dt)
    qn = jnp.sum(q * q, axis=1, dtype=dt)[:, None]
    n_q, c = queries.shape[0], corpus_blocks.shape[1]

    def step(carry, blk):
        best_s, best_i = carry
        x, xn, base = blk
        dot = jax.lax.dot_general(q, x.astype(dt), (((1,), (1,)), ((), ())),
                                  precision=prec, preferred_element_type=dt)
        s = (qn + xn.astype(dt)[None, :] - 2 * dot).astype(jnp.float32)
        neg, pos = jax.lax.top_k(-jnp.concatenate([best_s, s], axis=1), kk)
        kept = jnp.take_along_axis(best_i, jnp.minimum(pos, kk - 1), axis=1)
        return (-neg, jnp.where(pos < kk, kept, base + pos - kk)), None

    n_blocks = corpus_blocks.shape[0]
    bases = jnp.arange(n_blocks, dtype=jnp.int32) * c
    init = (jnp.full((n_q, kk), jnp.inf, jnp.float32),
            jnp.full((n_q, kk), -1, jnp.int32))
    (best_s, best_i), _ = jax.lax.scan(step, init,
                                       (corpus_blocks, norm_blocks, bases))
    return best_s, best_i


def _blocks(corpus: np.ndarray, low: bool):
    """The corpus on the device as (n_blocks, C, D) with row norms; padding
    rows get an infinite norm so they never rank."""
    n, d = corpus.shape
    n_blocks = -(-n // _C_BLOCK)
    pad = n_blocks * _C_BLOCK - n
    x = jnp.asarray(np.concatenate(
        [corpus, np.zeros((pad, d), np.float32)]) if pad else corpus)
    if low:
        xn = jnp.sum(jnp.square(x.astype(jnp.bfloat16)), axis=1,
                     dtype=jnp.bfloat16).astype(jnp.float32)
    else:
        xn = jnp.sum(jnp.square(x), axis=1)
    xn = jnp.where(jnp.arange(n_blocks * _C_BLOCK) < n, xn, jnp.inf)
    return (x.reshape(n_blocks, _C_BLOCK, d),
            xn.reshape(n_blocks, _C_BLOCK))


def exact_sq_l2(corpus: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Exact squared L2 (float64) between each query and the rows ``ids``
    (Q, k) names."""
    x = corpus[ids].astype(np.float64)
    return np.sum((x - queries[:, None, :].astype(np.float64)) ** 2, axis=2)


def topk(corpus: np.ndarray, queries: np.ndarray, k: int, *,
         precision: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """(ids (Q, k) int64, squared distances (Q, k) float64), ascending."""
    low = {"float32": False, "bfloat16": True}[precision]
    kk = k if low else k + SHORTLIST_EXTRA
    xb, nb = _blocks(corpus, low)
    out_ids, out_d = [], []
    for s in range(0, len(queries), _Q_BLOCK):
        q = queries[s:s + _Q_BLOCK]
        n_q = len(q)
        if n_q < _Q_BLOCK:      # one program shape for every block
            q = np.concatenate([q, np.zeros((_Q_BLOCK - n_q, q.shape[1]),
                                            np.float32)])
        score, rows = _scan(xb, nb, jnp.asarray(q), kk=kk, low=low)
        score = np.asarray(score)[:n_q].astype(np.float64)
        rows = np.asarray(rows)[:n_q].astype(np.int64)
        if low:
            out_ids.append(rows)
            out_d.append(score)
            continue
        d2 = exact_sq_l2(corpus, queries[s:s + n_q], rows)
        order = np.lexsort((rows, d2), axis=1)[:, :k]
        out_ids.append(np.take_along_axis(rows, order, axis=1))
        out_d.append(np.take_along_axis(d2, order, axis=1))
    del xb, nb
    return np.concatenate(out_ids), np.concatenate(out_d)

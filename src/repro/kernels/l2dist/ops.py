"""jit'd wrapper for the exact-L2 kernel (padding glue)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.l2dist.l2dist import l2dist
from repro.kernels.l2dist.ref import l2dist_ref


@functools.partial(jax.jit, static_argnames=("use_kernel", "block_q",
                                             "block_n"))
def l2_distances(queries: jax.Array, vectors: jax.Array, *,
                 use_kernel: bool = True,
                 block_q: int = 128, block_n: int = 512) -> jax.Array:
    if not use_kernel:
        return l2dist_ref(queries, vectors)
    b, d = queries.shape
    n, _ = vectors.shape
    bq = min(block_q, b)
    bn = min(block_n, n)
    pb, pn = (-b) % bq, (-n) % bn
    if pb:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pb, d), queries.dtype)], 0)
    if pn:
        vectors = jnp.concatenate(
            [vectors, jnp.zeros((pn, d), vectors.dtype)], 0)
    out = l2dist(queries, vectors, block_q=bq, block_n=bn)
    return out[:b, :n]

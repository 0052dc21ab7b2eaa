"""Product quantisation (paper §2.2): codebook training (k-means per
sub-space, vectorised over sub-spaces), encoding, and the ADC distance-table
machinery of Eq. (1)."""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PQCodebook:
    """codebooks: (M, K, dsub) — M sub-spaces, K=2^nbits centroids each."""

    codebooks: jax.Array

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def k(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]


def _split_subspaces(x: jax.Array, m: int) -> jax.Array:
    n, d = x.shape
    assert d % m == 0, f"dim {d} not divisible by M={m}"
    return x.reshape(n, m, d // m).transpose(1, 0, 2)          # (M, N, dsub)


def train_codebooks(rng: jax.Array, data: jax.Array, m: int,
                    nbits: int = 8, iters: int = 12) -> PQCodebook:
    """Vectorised per-sub-space k-means (Lloyd), k-means|| style sample init."""
    return PQCodebook(codebooks=_train(rng, data, m, 2 ** nbits, iters))


# One program each: run op by op, the (M, N, K) distance block would be
# materialised (32 GB at N = 1M, M = 32, K = 256); compiled, it fuses into
# the argmin (about 1 GB of temporaries on a v5e).
@functools.partial(jax.jit, static_argnames=("m", "k", "iters"))
def _train(rng: jax.Array, data: jax.Array, m: int, k: int,
           iters: int) -> jax.Array:
    sub = _split_subspaces(data.astype(jnp.float32), m)        # (M, N, ds)
    n = sub.shape[1]
    init_idx = jax.random.choice(rng, n, (k,), replace=n < k)
    centers = sub[:, init_idx]                                 # (M, K, ds)

    def step(centers, _):
        # assign: (M, N) nearest center per sub-vector
        d2 = (jnp.sum(sub ** 2, -1)[:, :, None]
              - 2.0 * jnp.einsum("mnd,mkd->mnk", sub, centers)
              + jnp.sum(centers ** 2, -1)[:, None, :])
        assign = jnp.argmin(d2, axis=-1)                       # (M, N)
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # (M, N, K)
        sums = jnp.einsum("mnk,mnd->mkd", onehot, sub)
        cnts = jnp.sum(onehot, axis=1)                         # (M, K)
        new = jnp.where(cnts[..., None] > 0,
                        sums / jnp.maximum(cnts[..., None], 1.0), centers)
        return new, None

    centers, _ = jax.lax.scan(step, centers, None, length=iters)
    return centers


def encode(cb: PQCodebook, data: jax.Array) -> jax.Array:
    """-> PQ codes (N, M) uint8 (nbits=8)."""
    return _encode(cb.codebooks, data)


@jax.jit
def _encode(codebooks: jax.Array, data: jax.Array) -> jax.Array:
    sub = _split_subspaces(data.astype(jnp.float32), codebooks.shape[0])
    d2 = (jnp.sum(sub ** 2, -1)[:, :, None]
          - 2.0 * jnp.einsum("mnd,mkd->mnk", sub, codebooks)
          + jnp.sum(codebooks ** 2, -1)[:, None, :])
    return jnp.argmin(d2, axis=-1).T.astype(jnp.uint8)         # (N, M)


def decode(cb: PQCodebook, codes: jax.Array) -> jax.Array:
    """Approximate reconstruction (tests)."""
    n, m = codes.shape
    rows = jnp.take_along_axis(
        cb.codebooks, codes.T[:, :, None].astype(jnp.int32), axis=1)
    return rows.transpose(1, 0, 2).reshape(n, -1)


def adc_lut(cb: PQCodebook, query: jax.Array) -> jax.Array:
    """Distance lookup table for one query: (M, K) squared-L2 per sub-space
    (paper step 1 — built on the accelerator)."""
    qs = query.astype(jnp.float32).reshape(cb.m, 1, cb.dsub)
    return jnp.sum((cb.codebooks - qs) ** 2, axis=-1)          # (M, K)


def adc_lut_batch(cb: PQCodebook, queries: jax.Array) -> jax.Array:
    """(B, D) -> (B, M, K), as one program (``jit__adc_lut_batch``)."""
    return _adc_lut_batch(cb.codebooks, queries)


@jax.jit
def _adc_lut_batch(codebooks: jax.Array, queries: jax.Array) -> jax.Array:
    m, _, dsub = codebooks.shape
    qs = queries.astype(jnp.float32).reshape(-1, m, 1, dsub)
    return jnp.sum((codebooks[None] - qs) ** 2, axis=-1)      # (B, M, K)


def adc_distances_ref(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Pure-jnp ADC scan (Eq. 1): sum_m lut[m, codes[n, m]].

    This is the oracle for the Pallas kernel in kernels/pq_adc."""
    m, k = lut.shape
    flat = lut.reshape(-1)
    idx = codes.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32)
                                     * k)[None, :]
    return jnp.sum(jnp.take(flat, idx), axis=-1)               # (N,)


def exact_l2(query: jax.Array, vectors: jax.Array) -> jax.Array:
    q = query.astype(jnp.float32)
    v = vectors.astype(jnp.float32)
    return jnp.sum((v - q[None, :]) ** 2, axis=-1)

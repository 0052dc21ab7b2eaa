"""Pallas TPU kernel: exact squared-L2 distances (re-rank step ⑧).

‖q−v‖² = ‖q‖² − 2·q·vᵀ + ‖v‖²: a (bq, D)x(D, bn) MXU matmul with a fused
row/col-norm epilogue.  Tiles are MXU-aligned (bq, bn multiples of 8/128
when shapes allow); D is kept whole per tile (ANNS dims are 96–384, well
under VMEM budget: bq*D + bn*D + bq*bn floats)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import pallas_interpret


def _l2_kernel(q_ref, v_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)           # (bq, D)
    v = v_ref[...].astype(jnp.float32)           # (bn, D)
    dots = jax.lax.dot_general(q, v, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    qn = jnp.sum(q * q, axis=-1)[:, None]
    vn = jnp.sum(v * v, axis=-1)[None, :]
    out_ref[...] = qn - 2.0 * dots + vn


def l2dist(queries: jax.Array, vectors: jax.Array, *, block_q: int = 128,
           block_n: int = 512) -> jax.Array:
    """(B, D) x (N, D) -> (B, N) f32.  B % block_q == 0, N % block_n == 0
    (ops.py pads)."""
    b, d = queries.shape
    n, dv = vectors.shape
    assert d == dv
    bq = min(block_q, b)
    bn = min(block_n, n)
    assert b % bq == 0 and n % bn == 0
    grid = (b // bq, n // bn)
    return pl.pallas_call(
        _l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=pallas_interpret(),
    )(queries, vectors)

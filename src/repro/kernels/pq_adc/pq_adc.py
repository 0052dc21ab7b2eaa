"""Pallas TPU kernel: PQ ADC scan (paper step ⑥, TPU-native — DESIGN.md §2).

The (M, K) distance LUT (≤ 128 KB for M ≤ 128, K = 256, f32) is pinned in
VMEM for the whole grid; PQ codes stream HBM→VMEM in (block_n, M) uint8
tiles.  Arithmetic intensity is ~2 FLOP/byte → the kernel is sized for
bandwidth: block_n * M bytes per grid step, one f32 row out.

Unlike the paper's CUDA kernel (one thread per dimension + coordinator
accumulation + spinlock hash dedup), the TPU formulation is a vectorised
flat-index gather over the VMEM-resident LUT with a sum over M — no atomics
exist in Pallas and none are needed (dedup is a separate sort-based pass).

The TPU compiler refuses all four kernels as written: the flat 1-D gathers
of ``pq_adc_scan`` / ``pq_adc_scan_topk`` ("Only 2D gather is supported")
and the batched gathers of ``pq_adc_scan_batch`` / ``pq_adc_scan_fused``
("Shape mismatch in input, indices and output").  They run only in the
CPU interpreter (``kernels/backend.py``); the serving path scans in XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import interpret_or_refuse


def _adc_kernel(codes_ref, lut_ref, out_ref, *, m: int, k: int):
    codes = codes_ref[...]                       # (block_n, M) uint8
    lut_flat = lut_ref[...].reshape(m * k)       # (M*K,) f32 in VMEM
    idx = codes.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32)
                                     * k)[None, :]
    vals = jnp.take(lut_flat, idx.reshape(-1), axis=0)
    out_ref[...] = jnp.sum(vals.reshape(codes.shape), axis=-1)


def pq_adc_scan(codes: jax.Array, lut: jax.Array, *,
                block_n: int = 2048) -> jax.Array:
    """codes (N, M) uint8, lut (M, K) f32 -> distances (N,) f32.

    N must be a multiple of block_n (callers pad; ops.py handles it)."""
    interpret = interpret_or_refuse("pq_adc_scan")
    n, m = codes.shape
    mk, k = lut.shape
    assert mk == m, (m, mk)
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_adc_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),   # stream codes
            pl.BlockSpec((m, k), lambda i: (0, 0)),         # LUT resident
        ],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(codes, lut)


def _adc_batch_kernel(codes_ref, luts_ref, out_ref, *, m: int, k: int,
                      n_q: int):
    """Batched-query ADC: codes tile (block_n, M) is read ONCE from HBM and
    scanned against ALL ``n_q`` LUTs resident in VMEM (n_q*M*K*4 B; 2 MB at
    B=64, M=32).  This is the §Perf hillclimb-A kernel: HBM traffic drops
    from B x codes-bytes (per-query scan) to 1 x codes-bytes per batch."""
    codes = codes_ref[...]                       # (block_n, M) uint8
    luts = luts_ref[...]                         # (n_q, M, K) f32
    idx = codes.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32)
                                     * k)[None, :]            # (bn, M)
    flat = luts.reshape(n_q, m * k)              # (B, M*K)
    vals = jnp.take(flat, idx.reshape(-1), axis=1)            # (B, bn*M)
    out_ref[...] = jnp.sum(
        vals.reshape(n_q, codes.shape[0], m), axis=-1)        # (B, bn)


def pq_adc_scan_batch(codes: jax.Array, luts: jax.Array, *,
                      block_n: int = 2048) -> jax.Array:
    """codes (N, M) uint8, luts (B, M, K) f32 -> distances (B, N) f32."""
    interpret = interpret_or_refuse("pq_adc_scan_batch")
    n, m = codes.shape
    b, mk, k = luts.shape
    assert mk == m and n % block_n == 0
    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_adc_batch_kernel, m=m, k=k, n_q=b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),
            pl.BlockSpec((b, m, k), lambda i: (0, 0, 0)),   # LUTs resident
        ],
        out_specs=pl.BlockSpec((b, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(codes, luts)


def _adc_topk_kernel(codes_ref, lut_ref, vals_ref, idx_ref, *,
                     m: int, k: int, topk: int, block_n: int, n: int):
    """Fused scan + per-block top-k: each grid step emits only (topk) pairs
    instead of block_n distances — the HBM write traffic drops by
    block_n/topk (the §Perf 'fused partial top-k' optimisation).

    Padding rows (global id >= ``n``) are masked to +inf BEFORE the
    per-block top-k: a mostly-padding final block must never evict genuine
    candidates from its partial top-k (they would be unrecoverable at the
    merge — the ISSUE-6 padding-eviction bug)."""
    i = pl.program_id(0)
    codes = codes_ref[...]
    lut_flat = lut_ref[...].reshape(m * k)
    idx = codes.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32)
                                     * k)[None, :]
    vals = jnp.take(lut_flat, idx.reshape(-1), axis=0)
    dist = jnp.sum(vals.reshape(codes.shape), axis=-1)      # (block_n,)
    gids = (jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0).squeeze(-1)
            + i * block_n)
    dist = jnp.where(gids < n, dist, jnp.inf)
    neg, pos = jax.lax.top_k(-dist, topk)
    vals_ref[...] = -neg
    idx_ref[...] = (pos + i * block_n).astype(jnp.int32)


def pq_adc_scan_topk(codes: jax.Array, lut: jax.Array, topk: int, *,
                     n: int = None, block_n: int = 2048):
    """Fused ADC scan + block-local top-k.

    ``n`` is the REAL row count (rows past it are padding, masked to +inf
    inside each block before its partial top-k).  Returns
    (vals (n_blocks*topk,), global_ids (n_blocks*topk,)); callers finish
    with one small lax.top_k merge (ops.pq_adc_topk)."""
    interpret = interpret_or_refuse("pq_adc_scan_topk")
    n_padded, m = codes.shape
    _, k = lut.shape
    if n is None:
        n = n_padded
    assert n_padded % block_n == 0 and topk <= block_n
    grid = (n_padded // block_n,)
    return pl.pallas_call(
        functools.partial(_adc_topk_kernel, m=m, k=k, topk=topk,
                          block_n=block_n, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, m), lambda i: (i, 0)),
            pl.BlockSpec((m, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((topk,), lambda i: (i,)),
            pl.BlockSpec((topk,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_padded // block_n * topk,), jnp.float32),
            jax.ShapeDtypeStruct((n_padded // block_n * topk,), jnp.int32),
        ],
        interpret=interpret,
    )(codes, lut)


def _adc_fused_kernel(rows_ref, codes_ref, queries_ref, cb_ref,
                      vals_ref, ids_ref, *scratch,
                      m: int, k: int, dsub: int, tk: int, lut_int8: bool):
    """One kernel per scan window: LUT build (query x codebooks) + ADC scan
    + block-local partial top-k (no full sort).

    * The (B, M, K) LUT is built ONCE at grid step 0 into VMEM scratch and
      stays resident across the whole grid — the BANG-style shared-memory
      pipeline (PAPERS.md) mapped to Pallas.
    * Candidate row-id tiles (B, block_s) stream through; pad slots
      (row id -1) score +inf BEFORE the partial top-k, so padding can
      never evict a genuine candidate (the bug fixed in _adc_topk_kernel,
      not ported here).
    * Only (dist, id) pairs exit to HBM: block_s slots in, tk pairs out.
    * ``lut_int8=True`` is the paper's fig10 accuracy-level variant: the
      LUT is quantised to int8 with a per-(query, subquantizer) scale and
      zero-point at build time (4x less VMEM), and dequantised per lookup
      with the accumulation kept in fp32 (the "fp32 merge").
    """
    @pl.when(pl.program_id(0) == 0)
    def _():
        q = queries_ref[...].astype(jnp.float32)
        q = q.reshape(q.shape[0], m, 1, dsub)                 # (B, M, 1, ds)
        lut = jnp.sum((cb_ref[...][None] - q) ** 2, axis=-1)  # (B, M, K)
        if lut_int8:
            lut8_ref, scale_ref, zp_ref = scratch
            lo = jnp.min(lut, axis=-1, keepdims=True)
            hi = jnp.max(lut, axis=-1, keepdims=True)
            scale = jnp.maximum(hi - lo, 1e-12) / 255.0
            lut8_ref[...] = (jnp.round((lut - lo) / scale)
                             - 128.0).astype(jnp.int8)
            scale_ref[...] = scale[..., 0]
            zp_ref[...] = lo[..., 0]
        else:
            scratch[0][...] = lut

    rows = rows_ref[...]                                      # (B, block_s)
    b, block_s = rows.shape
    rsafe = jnp.maximum(rows, 0)
    crow = jnp.take(codes_ref[...], rsafe.reshape(-1),
                    axis=0).reshape(b, block_s, m)            # (B, bs, M)
    idx = (crow.astype(jnp.int32)
           + (jnp.arange(m, dtype=jnp.int32) * k)[None, None, :]
           + (jnp.arange(b, dtype=jnp.int32) * (m * k))[:, None, None])
    if lut_int8:
        lut8_ref, scale_ref, zp_ref = scratch
        g = jnp.take(lut8_ref[...].reshape(-1), idx.reshape(-1),
                     axis=0).reshape(b, block_s, m).astype(jnp.float32)
        # dequantise per element, accumulate in fp32 (the "fp32 merge")
        dist = jnp.sum((g + 128.0) * scale_ref[...][:, None, :]
                       + zp_ref[...][:, None, :], axis=-1)
    else:
        g = jnp.take(scratch[0][...].reshape(-1), idx.reshape(-1), axis=0)
        dist = jnp.sum(g.reshape(b, block_s, m), axis=-1)     # (B, bs)
    dist = jnp.where(rows >= 0, dist, jnp.inf)
    neg, pos = jax.lax.top_k(-dist, tk)
    vals_ref[...] = -neg
    # pad slots carry row id -1 — an explicit "no candidate" marker the
    # merge keeps attached to its +inf distance
    ids_ref[...] = jnp.take_along_axis(rows, pos, axis=1)


def pq_adc_scan_fused(codes: jax.Array, queries: jax.Array,
                      codebooks: jax.Array, rows: jax.Array, topk: int, *,
                      block_s: int = 2048, lut_int8: bool = False):
    """Fused LUT->ADC->top-k over per-query candidate rows.

    codes (N, M) uint8 resident; queries (B, M*dsub) f32 (rotation already
    applied); codebooks (M, K, dsub) f32; rows (B, S) int32 candidate row
    ids (-1 = pad, S a multiple of ``block_s``).  Returns
    (vals (B, n_blocks*tk), ids (B, n_blocks*tk)) with tk =
    min(topk, block_s); callers finish with one small merge
    (ops.pq_adc_fused_topk)."""
    interpret = interpret_or_refuse("pq_adc_scan_fused")
    n, m = codes.shape
    mk, k, dsub = codebooks.shape
    b, s = rows.shape
    assert mk == m and s % block_s == 0, (m, mk, s, block_s)
    tk = min(topk, block_s)
    grid = (s // block_s,)
    if lut_int8:
        scratch = [pltpu.VMEM((b, m, k), jnp.int8),
                   pltpu.VMEM((b, m), jnp.float32),
                   pltpu.VMEM((b, m), jnp.float32)]
    else:
        scratch = [pltpu.VMEM((b, m, k), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_adc_fused_kernel, m=m, k=k, dsub=dsub, tk=tk,
                          lut_int8=lut_int8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, block_s), lambda i: (0, i)),    # stream rows
            pl.BlockSpec((n, m), lambda i: (0, 0)),          # codes resident
            pl.BlockSpec(queries.shape, lambda i: (0, 0)),   # resident
            pl.BlockSpec((m, k, dsub), lambda i: (0, 0, 0)),  # resident
        ],
        out_specs=[
            pl.BlockSpec((b, tk), lambda i: (0, i)),
            pl.BlockSpec((b, tk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s // block_s * tk), jnp.float32),
            jax.ShapeDtypeStruct((b, s // block_s * tk), jnp.int32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(rows, codes, queries, codebooks)

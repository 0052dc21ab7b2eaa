"""jit'd wrappers for the ADC kernels (padding + merge glue).

``use_kernel=True`` runs the Pallas kernel, which exists only in the CPU
interpreter (the TPU compiler refuses it; ``kernels/backend.py`` raises on
any other backend).  ``use_kernel=False`` is the XLA form the serving path
runs on every backend, and the oracle the kernels are tested against."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.pq_adc.pq_adc import (pq_adc_scan, pq_adc_scan_batch,
                                         pq_adc_scan_fused, pq_adc_scan_topk)
from repro.kernels.pq_adc.ref import (build_luts_ref, pq_adc_batch_ref,
                                      pq_adc_ref)


def smallest_k(vals: jax.Array, ids: jax.Array, k: int):
    """The ``k`` smallest ``(val, id)`` pairs along the last axis, ordered
    by value, then id.  ``lax.top_k`` breaks ties by array position, and
    after a shard merge's all-gather that position follows the shards,
    not the ids.  PQ distances tie often (vectors of one cluster share
    codes), so the dense, fused and sharded scans all select their top-n
    by this one total order, whatever layout the selection sees."""
    v, i = jax.lax.sort((vals, ids), dimension=vals.ndim - 1, num_keys=2)
    return v[..., :k], i[..., :k]


def _pad_codes(codes: jax.Array, block_n: int):
    n = codes.shape[0]
    pad = (-n) % block_n
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros((pad, codes.shape[1]), codes.dtype)], axis=0)
    return codes, n, pad


@functools.partial(jax.jit, static_argnames=("block_n", "use_kernel"))
def pq_adc(codes: jax.Array, lut: jax.Array, *, block_n: int = 2048,
           use_kernel: bool = True) -> jax.Array:
    """distances (N,) f32; ``use_kernel=False`` is the XLA form
    (identical results)."""
    if not use_kernel:
        return pq_adc_ref(codes, lut)
    padded, n, pad = _pad_codes(codes, min(block_n, max(codes.shape[0], 8)))
    bn = min(block_n, padded.shape[0])
    out = pq_adc_scan(padded, lut, block_n=bn)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block_n", "use_kernel"))
def pq_adc_batch(codes: jax.Array, luts: jax.Array, *, block_n: int = 2048,
                 use_kernel: bool = True):
    """Batched queries: (N, M) x (B, M, K) -> (B, N) distances."""
    if not use_kernel:
        return pq_adc_batch_ref(codes, luts)
    padded, n, pad = _pad_codes(codes, min(block_n, max(codes.shape[0], 8)))
    bn = min(block_n, padded.shape[0])
    out = pq_adc_scan_batch(padded, luts, block_n=bn)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("topk", "block_n", "use_kernel"))
def pq_adc_topk_batch(codes: jax.Array, luts: jax.Array, topk: int, *,
                      mask: jax.Array = None, block_n: int = 2048,
                      use_kernel: bool = True):
    """Batched fused scan + per-query (optionally masked) top-k.

    codes (N, M) x luts (B, M, K) [x mask (B, N) bool] ->
    (dists (B, tk), row indices (B, tk)) ascending, tk = min(topk, N).
    ``mask`` is the executor's per-query candidate membership: False rows
    (other queries' candidates, padding) score +inf and sort last, as in
    the executor's window scan (``core.distributed``)."""
    d = pq_adc_batch(codes, luts, block_n=block_n, use_kernel=use_kernel)
    if mask is not None:
        d = jnp.where(mask, d, jnp.inf)
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    return smallest_k(d, pos, min(topk, d.shape[1]))


@functools.partial(jax.jit, static_argnames=("topk", "block_n", "use_kernel"))
def pq_adc_topk(codes: jax.Array, lut: jax.Array, topk: int, *,
                block_n: int = 2048, use_kernel: bool = True):
    """Fused scan + top-k: returns (dists (tk,), ids (tk,)) ascending with
    tk = min(topk, N) — only REAL rows, never padding.

    Two ISSUE-6 fixes live here and in the kernel:
    * padding rows are masked to +inf INSIDE each block before its partial
      top-k (``n`` rides into ``pq_adc_scan_topk``), so a mostly-padding
      final block can't evict genuine candidates before the merge;
    * the output is truncated to min(topk, N): with the per-block mask in
      place every block keeps its real rows preferentially, so the first
      min(topk, N) merged entries are guaranteed finite — +inf padding
      ids can no longer leak into rerank candidate lists when N < topk.
    """
    n = codes.shape[0]
    tk_out = min(topk, n)
    if not use_kernel:
        d = pq_adc_ref(codes, lut)
        neg, ids = jax.lax.top_k(-d, tk_out)
        return -neg, ids
    padded, n, pad = _pad_codes(codes, min(block_n, max(n, 8)))
    bn = min(block_n, padded.shape[0])
    tk = min(topk, bn)
    vals, ids = pq_adc_scan_topk(padded, lut, tk, n=n, block_n=bn)
    neg, pos = jax.lax.top_k(-vals, tk_out)
    return -neg, ids[pos]


@jax.jit
def quantize_luts(luts: jax.Array):
    """fig10 accuracy levels: asymmetric int8 quantisation of the ADC
    tables, per (query, subquantizer).  (B, M, K) f32 ->
    (q8 (B, M, K) int8, scale (B, M) f32, zp (B, M) f32);
    dequant is (q8 + 128) * scale + zp, accumulated in fp32."""
    lo = jnp.min(luts, axis=-1, keepdims=True)
    hi = jnp.max(luts, axis=-1, keepdims=True)
    scale = jnp.maximum(hi - lo, 1e-12) / 255.0
    q8 = (jnp.round((luts - lo) / scale) - 128.0).astype(jnp.int8)
    return q8, scale[..., 0], lo[..., 0]


# the LUT build is its OWN dispatch on purpose: when the (B, M, K) table
# expression is traced into the same jit as the gather below, XLA:CPU fuses
# it INTO the gather's loop fusion and recomputes sum((cb - q)^2) per
# lookup (~3x slower; optimization_barrier doesn't help — it materialises
# the 67 MB gather instead).  Built separately, the table lands as a jit
# PARAMETER and the scan compiles to one gather+reduce loop fusion.
_build_luts = jax.jit(build_luts_ref)


@functools.partial(jax.jit, static_argnames=("topk",))
def _fused_rows_scan(codes, luts, rows, topk: int):
    """One dispatch: u8 row gather + LUT gather + sum over M + pad mask +
    per-query top-k over the candidate segment.  ``luts`` MUST be a traced
    parameter (see _build_luts)."""
    b, s = rows.shape
    m = codes.shape[1]
    k = luts.shape[-1]
    rsafe = jnp.maximum(rows, 0)
    crow = codes.at[rsafe].get(mode="promise_in_bounds")      # (B, S, M)
    idx = (crow.astype(jnp.int32)
           + (jnp.arange(m, dtype=jnp.int32) * k)[None, None, :]
           + (jnp.arange(b, dtype=jnp.int32) * (m * k))[:, None, None])
    flat = luts.reshape(-1)
    d = jnp.sum(flat.at[idx].get(mode="promise_in_bounds"), axis=-1)
    d = jnp.where(rows >= 0, d, jnp.inf)
    # rows carries -1 at pad slots, so ids inherit the "no candidate"
    # marker for free (+inf distance rides along)
    return smallest_k(d, rows, min(topk, s))


@functools.partial(jax.jit, static_argnames=("topk",))
def _fused_rows_scan_int8(codes, q8, scale, zp, rows, topk: int):
    """int8-LUT variant of _fused_rows_scan: gather int8 table entries,
    dequantise per element, accumulate in fp32 (the "fp32 merge")."""
    b, s = rows.shape
    m = codes.shape[1]
    k = q8.shape[-1]
    rsafe = jnp.maximum(rows, 0)
    crow = codes.at[rsafe].get(mode="promise_in_bounds")
    idx = (crow.astype(jnp.int32)
           + (jnp.arange(m, dtype=jnp.int32) * k)[None, None, :]
           + (jnp.arange(b, dtype=jnp.int32) * (m * k))[:, None, None])
    g = q8.reshape(-1).at[idx].get(
        mode="promise_in_bounds").astype(jnp.float32)         # (B, S, M)
    d = jnp.sum((g + 128.0) * scale[:, None, :] + zp[:, None, :], axis=-1)
    d = jnp.where(rows >= 0, d, jnp.inf)
    return smallest_k(d, rows, min(topk, s))


def pq_adc_fused_topk(codes: jax.Array, queries: jax.Array,
                      codebooks: jax.Array, rows: jax.Array, topk: int, *,
                      lut_int8: bool = False, use_kernel: bool = True,
                      block_s: int = 2048):
    """The fused query pipeline (ISSUE-6 tentpole): LUT build -> ADC scan
    -> partial top-k over each query's OWN candidate rows, one device
    round-trip per scan window.

    codes (N, M) uint8 (the whole HBM tier — no per-window candidate
    gather); queries (B, M*dsub) f32 with any OPQ rotation already
    applied; codebooks (M, K, dsub) f32; rows (B, S) int32 global row ids,
    -1 = pad, each query's ids sorted ascending (makes top-k tie-breaks
    match the dense masked scan bit-exactly).  Returns
    (dists (B, tk), row ids (B, tk)) ascending, tk = min(topk, S); slots
    past a query's candidate count come back as (+inf, -1), never as a
    padding row id.

    ``use_kernel=True`` runs the single Pallas kernel (LUT resident in
    VMEM across the grid, int8 scratch under ``lut_int8``; CPU interpreter
    only).  ``use_kernel=False`` is the XLA form the serving path runs: a
    tiny LUT-build dispatch plus ONE fused gather/scan/top-k jit."""
    b, s = rows.shape
    tk_out = min(topk, s)
    if not use_kernel:
        luts = _build_luts(codebooks, queries)
        if lut_int8:
            q8, scale, zp = quantize_luts(luts)
            return _fused_rows_scan_int8(codes, q8, scale, zp, rows, tk_out)
        return _fused_rows_scan(codes, luts, rows, tk_out)
    bs = min(block_s, max(s, 8))
    pad = (-s) % bs
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.full((b, pad), -1, rows.dtype)], axis=1)
    vals, ids = pq_adc_scan_fused(codes, queries, codebooks, rows, tk_out,
                                  block_s=bs, lut_int8=lut_int8)
    neg, pos = jax.lax.top_k(-vals, min(tk_out, vals.shape[1]))
    return -neg, jnp.take_along_axis(ids, pos, axis=1)

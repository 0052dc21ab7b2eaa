"""Distributed FusionANNS scan: PQ codes row-sharded across every device's
HBM (the paper's "pinned in GPU HBM" tier, scaled to a pod — DESIGN.md §2).

Per query batch: each device ADC-scans its code shard, takes a *local*
top-n, and one small ``all_gather`` of (dist, global-id) pairs merges
shards — vector contents never cross the interconnect, exactly the paper's
ID-only invariant.

The scan is the XLA form on every backend: the TPU compiler refuses the
Pallas ADC kernels (``kernels/pq_adc``), and on the CPU they only run in
the interpreter the parity tests use."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.pq_adc.ref import pq_adc_ref
from repro.models.layers import ShardCtx
from repro.sharding.spec import shard_map


def window_scan_ready(*arrays) -> bool:
    """True when every device buffer backing a window's scan outputs has
    landed (jax async dispatch done).  Used by the futures layer for
    non-blocking progress (``BatchTicket.poll``): a window whose scan is
    ready can be retired without stalling the host.  Conservatively falls
    back to True (retire-and-block, still correct) on runtimes without
    ``jax.Array.is_ready``."""
    for a in arrays:
        is_ready = getattr(a, "is_ready", None)
        if is_ready is None:
            continue
        try:
            if not is_ready():
                return False
        except Exception:       # noqa: BLE001 — deleted/donated buffers
            continue
    return True


def replicate_to_mesh(x: jax.Array, ctx: ShardCtx) -> jax.Array:
    """Commit ``x`` replicated onto ``ctx.mesh``'s devices.

    On a full mesh this is what jit would do implicitly for an uncommitted
    operand; on a SUB-mesh (multi-replica serving: one replica owns a
    disjoint device group carved from the shared mesh) it matters — an
    uncommitted array lives on the process default device, which may not
    belong to this replica's group at all, and compute-follows-data would
    otherwise drag the scan off the replica's devices (contending with a
    sibling replica's scan).  No-op without a mesh."""
    if ctx.mesh is None:
        return x
    spec = P(*((None,) * x.ndim))
    return jax.device_put(x, NamedSharding(ctx.mesh, spec))


@functools.lru_cache(maxsize=64)
def _sharded_program(body, mesh: Mesh, in_specs, out_specs, **static):
    """``body`` as one jitted ``shard_map`` program per (mesh, static
    arguments).  Called outside ``jit``, ``shard_map`` runs its body op by
    op and compiles dozens of small programs on every call."""
    return jax.jit(shard_map(functools.partial(body, **static), mesh=mesh,
                             in_specs=in_specs, out_specs=out_specs))


def _gather_merge_batched(vals, gids, axes, n_shards: int, tk_out: int):
    """Shared tail of the batched shard bodies: all_gather the per-shard
    (dist, global-id) pairs along the query-local axis and merge by the
    same (dist, id) order each shard selected with."""
    from repro.kernels.pq_adc.ops import smallest_k
    if n_shards > 1:
        vals = jax.lax.all_gather(vals, axes, axis=1, tiled=True)
        gids = jax.lax.all_gather(gids, axes, axis=1, tiled=True)
    return smallest_k(vals, gids, tk_out)


def _local_scan_topn(codes, lut, top_n: int, axes, n_shards: int):
    n_loc = codes.shape[0]
    dist = pq_adc_ref(codes, lut)                     # (n_loc,) f32
    tk = min(top_n, n_loc)
    neg, idx = jax.lax.top_k(-dist, tk)
    me = jax.lax.axis_index(axes) if n_shards > 1 else 0
    gids = idx + me * n_loc
    vals = -neg
    if n_shards > 1:
        vals = jax.lax.all_gather(vals, axes, axis=0, tiled=True)
        gids = jax.lax.all_gather(gids, axes, axis=0, tiled=True)
    neg, pos = jax.lax.top_k(-vals, tk)
    return -neg, gids[pos]


def sharded_adc_topn(codes: jax.Array, lut: jax.Array, top_n: int,
                     ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """codes (N, M) uint8 sharded over ``corpus`` axes; lut (M, K) f32
    replicated -> (dists (top_n,), global ids (top_n,)) replicated."""
    if ctx.mesh is None:
        dist = pq_adc_ref(codes, lut)
        neg, ids = jax.lax.top_k(-dist, min(top_n, codes.shape[0]))
        return -neg, ids
    axes = ctx.rules.corpus
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes_t:
        n_shards *= ctx.mesh.shape[a]
    body = functools.partial(_local_scan_topn, top_n=top_n, axes=axes_t,
                             n_shards=n_shards)
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(axes, None), P(None, None)),
        out_specs=(P(), P()),
    )(codes, lut)


def _local_scan_topn_blocked(codes, luts, top_n: int, axes, n_shards: int,
                             block_n: int = 65536):
    """§Perf hillclimb A (jnp form): scan code BLOCKS, scoring all B
    queries per block in one flat gather, with a running per-query top-n —
    the Pallas `pq_adc_batch` kernel is the VMEM-resident version of this
    loop (LUTs + accumulators never leave VMEM)."""
    n_loc, m = codes.shape
    b, _, k = luts.shape
    bn = min(block_n, n_loc)
    n_blocks = n_loc // bn
    assert n_blocks * bn == n_loc, (n_loc, bn)
    flat = luts.reshape(b, m * k)
    tk = min(top_n, bn)

    def body(carry, blk_idx):
        run_v, run_i = carry
        blk = jax.lax.dynamic_slice_in_dim(codes, blk_idx * bn, bn)
        idx = blk.astype(jnp.int32) + (jnp.arange(m, dtype=jnp.int32)
                                       * k)[None, :]
        vals = jnp.take(flat, idx.reshape(-1), axis=1)        # (B, bn*M)
        dist = jnp.sum(vals.reshape(b, bn, m), axis=-1)       # (B, bn)
        neg, pos = jax.lax.top_k(-dist, tk)
        ids = pos + blk_idx * bn
        cat_v = jnp.concatenate([run_v, -neg], axis=1)
        cat_i = jnp.concatenate([run_i, ids], axis=1)
        neg2, pos2 = jax.lax.top_k(-cat_v, tk)
        return (-neg2, jnp.take_along_axis(cat_i, pos2, axis=1)), None

    init = (jnp.full((b, tk), jnp.inf, jnp.float32),
            jnp.full((b, tk), -1, jnp.int32))
    (vals, ids), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
    me = jax.lax.axis_index(axes) if n_shards > 1 else 0
    gids = ids + me * n_loc
    return _gather_merge_batched(vals, gids, axes, n_shards, tk)


def sharded_adc_topn_batch(codes: jax.Array, luts: jax.Array, top_n: int,
                           ctx: ShardCtx, *, blocked: bool = True
                           ) -> Tuple[jax.Array, jax.Array]:
    """Batched queries: luts (B, M, K) replicated -> ((B, top_n) x2).

    The scan is the bandwidth-bound stage; queries amortise the code
    traffic (each code byte is read once per *batch*, not per query).
    ``blocked=False`` falls back to the per-query map (the §Perf baseline).
    """
    if ctx.mesh is None:
        def one(lut):
            d = pq_adc_ref(codes, lut)
            neg, ids = jax.lax.top_k(-d, min(top_n, codes.shape[0]))
            return -neg, ids
        return jax.lax.map(one, luts)
    axes = ctx.rules.corpus
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes_t:
        n_shards *= ctx.mesh.shape[a]

    if blocked:
        def body(codes_l, luts_l):
            return _local_scan_topn_blocked(codes_l, luts_l, top_n, axes_t,
                                            n_shards)
    else:
        def body(codes_l, luts_l):
            def one(lut):
                return _local_scan_topn(codes_l, lut, top_n, axes_t,
                                        n_shards)
            return jax.lax.map(one, luts_l)

    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(axes, None), P(None, None, None)),
        out_specs=(P(None, None), P(None, None)),
    )(codes, luts)


def _local_scan_topn_window(codes, rows, luts, mask, top_n: int, axes,
                            n_shards: int):
    """Per-shard body of the executor's windowed scan.  ``rows`` (bucket,)
    holds the window's candidate union as GLOBAL code rows (replicated):
    this shard scores the bucket positions whose rows it holds, masks the
    rest, non-members and padding to +inf, takes a per-query top-n, and
    all_gathers only (distance, bucket-position) pairs — no code row
    crosses the interconnect."""
    from repro.kernels.pq_adc.ops import smallest_k
    from repro.kernels.pq_adc.ref import pq_adc_batch_ref
    n_loc = codes.shape[0]
    me = jax.lax.axis_index(axes) if n_shards > 1 else 0
    local = rows - me * n_loc
    mine = (local >= 0) & (local < n_loc)
    cand = jnp.take(codes, jnp.clip(local, 0, n_loc - 1), axis=0)
    dist = pq_adc_batch_ref(cand, luts)                       # (B, bucket)
    dist = jnp.where(mask & mine[None, :], dist, jnp.inf)
    tk = min(top_n, rows.shape[0])
    vals, pos = smallest_k(
        dist, jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1), tk)
    return _gather_merge_batched(vals, pos, axes, n_shards, tk)


_window_scan_one = jax.jit(_local_scan_topn_window,
                           static_argnames=("top_n", "axes", "n_shards"))


def sharded_adc_topn_window(codes: jax.Array, rows: jax.Array,
                            luts: jax.Array, mask: jax.Array, top_n: int,
                            ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """Executor stage ⑤: candidate-bucket scan with per-query membership.

    codes (N, M) uint8 (row-sharded over the ``corpus`` axes on a mesh);
    rows (U,) int32 the window's candidate union as code rows (padding:
    any row, masked); luts (B, M, K); mask (B, U) bool, True where bucket
    slot u is one of query b's candidates -> (dists (B, tk), bucket
    positions (B, tk)) replicated, tk = min(top_n, U).  Masked-out slots
    surface as +inf.  One device runs the same body as one shard: every
    shard scores the whole (B, U) bucket shape and selects by (distance,
    position), so sharded == unsharded exactly."""
    if ctx.mesh is None:
        return _window_scan_one(codes, rows, luts, mask, top_n=top_n,
                                axes=(), n_shards=1)
    axes = ctx.rules.corpus
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes_t:
        n_shards *= ctx.mesh.shape[a]
    return _sharded_program(
        _local_scan_topn_window, ctx.mesh,
        (P(axes, None), P(None), P(None, None, None), P(None, None)),
        (P(None, None), P(None, None)),
        top_n=top_n, axes=axes_t, n_shards=n_shards)(codes, rows, luts, mask)


def _local_scan_topn_rows(codes, queries, codebooks, rows, top_n: int,
                          axes, n_shards: int, lut_int8: bool):
    """Per-shard body of the FUSED windowed scan: each query scans its own
    candidate-row list.  ``rows`` holds GLOBAL row ids (replicated); this
    shard scores only the ids that land in its local range, surfaces the
    rest as +inf, and all_gathers (distance, global-id) pairs — still the
    paper's ID-only interconnect invariant."""
    from repro.kernels.pq_adc.ops import pq_adc_fused_topk
    n_loc = codes.shape[0]
    me = jax.lax.axis_index(axes) if n_shards > 1 else 0
    local = rows - me * n_loc
    mine = (rows >= 0) & (local >= 0) & (local < n_loc)
    # keep ascending-id order inside the shard: misses -> -1 pads
    local = jnp.where(mine, local, -1)
    vals, lids = pq_adc_fused_topk(codes, queries, codebooks, local,
                                   top_n, use_kernel=False,
                                   lut_int8=lut_int8)
    gids = jnp.where(lids >= 0, lids + me * n_loc, -1)
    return _gather_merge_batched(vals, gids, axes, n_shards,
                                 min(top_n, rows.shape[1]))


def sharded_adc_topn_rows(codes: jax.Array, queries: jax.Array,
                          codebooks: jax.Array, rows: jax.Array,
                          top_n: int, ctx: ShardCtx, *,
                          lut_int8: bool = False
                          ) -> Tuple[jax.Array, jax.Array]:
    """Executor stage ⑤, fused form (`fused=` plan knob): LUT build + ADC
    scan + partial top-k in one pipeline per shard, per-query candidate
    ROW LISTS instead of a dense (B, N) mask.

    codes (N, M) uint8 row-sharded over the ``corpus`` axes; queries
    (B, M*dsub) f32 (OPQ rotation pre-applied) and codebooks (M, K, dsub)
    replicated; rows (B, S) int32 GLOBAL row ids, -1 = pad, ascending per
    query -> (dists (B, tk), GLOBAL ids (B, tk)) replicated with
    tk = min(top_n, S).  Empty slots come back as (+inf, -1).  Unlike
    `sharded_adc_topn_window`, the ids are global rows, not bucket
    positions — no candidate union/gather ever materialises."""
    if ctx.mesh is None:
        from repro.kernels.pq_adc.ops import pq_adc_fused_topk
        return pq_adc_fused_topk(codes, queries, codebooks, rows, top_n,
                                 use_kernel=False, lut_int8=lut_int8)
    axes = ctx.rules.corpus
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes_t:
        n_shards *= ctx.mesh.shape[a]
    return _sharded_program(
        _local_scan_topn_rows, ctx.mesh,
        (P(axes, None), P(None, None), P(None, None, None), P(None, None)),
        (P(None, None), P(None, None)),
        top_n=top_n, axes=axes_t, n_shards=n_shards,
        lut_int8=lut_int8)(codes, queries, codebooks, rows)

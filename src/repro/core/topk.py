"""Two-level distributed top-k (shard-local top-k -> all_gather -> merge).

This is the collective pattern FusionANNS needs for its sharded ADC scan
(step 7: per-shard candidate lists merged into the global top-n), and it is
reused by the recsys retrieval/serving steps (score vs 10^6 items).  Only
(k x n_shards) (value, id) pairs cross the interconnect — never the scores.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.layers import ShardCtx
from repro.sharding.spec import shard_map

Axes = Union[str, Tuple[str, ...]]


def _axes_tuple(axes: Axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def local_topk_merge(vals, idx, k):
    """Merge per-shard (vals, idx) of shape (..., n*k) into global top-k."""
    v, pos = jax.lax.top_k(vals, k)
    gi = jnp.take_along_axis(idx, pos, axis=-1)
    return v, gi


def sharded_topk(scores: jax.Array, k: int, ctx: ShardCtx, *,
                 shard_axes: Axes, batch_axes: Axes = "batch",
                 largest: bool = True) -> Tuple[jax.Array, jax.Array]:
    """scores (B, V) with V sharded over ``shard_axes`` -> (vals, global_ids)
    each (B, k), replicated over ``shard_axes``.

    ``shard_axes`` are *physical* mesh axis names; ``batch_axes`` is the
    logical rule name for the batch dim (resolved via ctx.rules).
    """
    sign = 1.0 if largest else -1.0
    if ctx.mesh is None:
        v, i = jax.lax.top_k(sign * scores, k)
        return sign * v, i
    axes = _axes_tuple(shard_axes)
    n_shards = 1
    for a in axes:
        n_shards *= ctx.mesh.shape[a]
    b_spec = getattr(ctx.rules, batch_axes) if isinstance(batch_axes, str) \
        and hasattr(ctx.rules, batch_axes) else batch_axes

    def body(s):
        v_loc = s.shape[-1]
        v, i = jax.lax.top_k(sign * s, min(k, v_loc))
        me = jax.lax.axis_index(axes)
        gi = i + me * v_loc
        if n_shards > 1:
            v = jax.lax.all_gather(v, axes, axis=-1, tiled=True)
            gi = jax.lax.all_gather(gi, axes, axis=-1, tiled=True)
        vv, gg = local_topk_merge(v, gi, k)
        return sign * vv, gg

    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=P(b_spec, axes),
        out_specs=(P(b_spec, None), P(b_spec, None)),
    )(scores)

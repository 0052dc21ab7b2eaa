"""GraphSAGE [arXiv:1706.02216] in pure JAX.

Message passing is ``gather(src) -> segment_sum(dst)`` over an edge index —
JAX has no CSR SpMM, so this IS the system's sparse layer (see kernel
taxonomy §GNN).  Three execution modes:

  * full-graph: edges (E, 2) + features (N, F); edges sharded over all mesh
    axes, per-shard partial aggregates all-reduced by GSPMD.
  * minibatch: dense sampled-neighborhood tensors from the uniform fanout
    sampler in ``repro.data.graphs`` (B, f0, F) / (B, f0, f1, F).
  * batched small graphs: block-diagonal flattening + per-graph readout.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.models.layers import ShardCtx, LOCAL_CTX
from repro.sharding.spec import Rules, shard_map


def init_sage(rng: jax.Array, cfg: GNNConfig,
              d_feat: Optional[int] = None,
              n_classes: Optional[int] = None) -> Dict[str, Any]:
    d_feat = d_feat or cfg.d_feat
    n_classes = n_classes or cfg.n_classes
    dims = [d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [n_classes]
    params: Dict[str, Any] = {"layers": []}
    keys = jax.random.split(rng, cfg.n_layers)
    for i in range(cfg.n_layers):
        k1, k2 = jax.random.split(keys[i])
        fan = dims[i]
        std = 1.0 / math.sqrt(fan)
        params["layers"].append({
            "w_self": std * jax.random.normal(k1, (dims[i], dims[i + 1]),
                                              jnp.float32),
            "w_neigh": std * jax.random.normal(k2, (dims[i], dims[i + 1]),
                                               jnp.float32),
            "b": jnp.zeros((dims[i + 1],), jnp.float32),
        })
    return params


def sage_param_specs(cfg: GNNConfig, r: Rules) -> Dict[str, Any]:
    # SAGE weights are tiny (d_feat x 128) and d_feat is rarely divisible by
    # the mesh (1433, 602, 100...): replicate, shard the *edges* instead.
    layer = {"w_self": P(None, None), "w_neigh": P(None, None), "b": P(None)}
    return {"layers": [dict(layer) for _ in range(cfg.n_layers)]}


def _mean_aggregate(h: jax.Array, edges: jax.Array, n_nodes: int,
                    ctx: ShardCtx, weights=None, dst_offset=None
                    ) -> jax.Array:
    """h (N, d), edges (E, 2) src->dst; returns mean over in-neighbors.

    ``weights`` (E,) lets the pipeline pad edge shards exactly (w=0 pads);
    ``dst_offset`` localises dst ids inside a dst-partitioned shard."""
    src, dst = edges[:, 0], edges[:, 1]
    if dst_offset is not None:
        dst = dst - dst_offset
    if weights is None:
        weights = jnp.ones((edges.shape[0],), h.dtype)
    msgs = jnp.take(h, src, axis=0) * weights[:, None].astype(h.dtype)
    agg = jax.ops.segment_sum(msgs, dst, num_segments=n_nodes)
    deg = jax.ops.segment_sum(weights.astype(h.dtype), dst,
                              num_segments=n_nodes)
    return agg / jnp.maximum(deg, 1.0)[:, None]


def _sage_layer(h_self, h_neigh, p, *, final: bool):
    out = (h_self @ p["w_self"].astype(h_self.dtype)
           + h_neigh @ p["w_neigh"].astype(h_self.dtype)
           + p["b"].astype(h_self.dtype))
    if final:
        return out
    out = jax.nn.relu(out)
    # L2 normalise (GraphSAGE §3.1 line 7)
    norm = jnp.linalg.norm(out.astype(jnp.float32), axis=-1, keepdims=True)
    return (out.astype(jnp.float32) / jnp.maximum(norm, 1e-6)).astype(out.dtype)


def sage_forward_full(params, feats, edges, cfg: GNNConfig,
                      ctx: ShardCtx = LOCAL_CTX, weights=None) -> jax.Array:
    """Full-graph forward: feats (N, F), edges (E, 2) -> logits (N, C)."""
    n_nodes = feats.shape[0]
    h = feats
    for i, p in enumerate(params["layers"]):
        h_neigh = _mean_aggregate(h, edges, n_nodes, ctx, weights)
        h = _sage_layer(h, h_neigh, p, final=(i == cfg.n_layers - 1))
    return h


def sage_forward_full_dstpart(params, feats, edges, weights,
                              cfg: GNNConfig, ctx: ShardCtx) -> jax.Array:
    """§Perf hillclimb B: dst-partitioned full-graph forward.

    Pipeline invariant: edges are range-partitioned by dst (device i holds
    exactly the edges whose dst lies in its node range; shards padded with
    w=0 edges).  Each device aggregates ONLY its own N/P nodes — the
    full-size partial-aggregate psum of the baseline disappears; the only
    collective left is the (N, d_hidden) all_gather of layer-1 outputs.
    """
    assert ctx.mesh is not None
    r = ctx.rules
    axes = r.corpus
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    n_shards = 1
    for a in axes_t:
        n_shards *= ctx.mesh.shape[a]
    n_nodes = feats.shape[0]
    assert n_nodes % n_shards == 0, (n_nodes, n_shards)
    n_loc = n_nodes // n_shards
    p1, p2 = params["layers"]
    assert cfg.n_layers == 2

    def body(feats, edges_l, w_l, p1, p2):
        me = jax.lax.axis_index(axes_t)
        lo = me * n_loc
        neigh = _mean_aggregate(feats, edges_l, n_loc, None, w_l,
                                dst_offset=lo)
        self_l = jax.lax.dynamic_slice_in_dim(feats, lo, n_loc)
        h1_l = _sage_layer(self_l, neigh, p1, final=False)
        # iteration B2: gather hidden states in 16 bits (halves the one
        # remaining collective; SAGE hiddens are L2-normalised, bf16-safe).
        # Shipped as u16 bit-patterns: integer collectives are immune to
        # the CPU backend's bf16->f32 float-normalisation (EXPERIMENTS.md
        # §Perf 0b), and TPU moves the same bytes either way.
        h1_bits = jax.lax.bitcast_convert_type(
            h1_l.astype(jnp.bfloat16), jnp.uint16)
        h1_g = jax.lax.all_gather(h1_bits, axes_t, axis=0, tiled=True)
        h1 = jax.lax.bitcast_convert_type(
            h1_g, jnp.bfloat16).astype(h1_l.dtype)                 # (N, d)
        neigh2 = _mean_aggregate(h1, edges_l, n_loc, None, w_l,
                                 dst_offset=lo)
        return _sage_layer(h1_l, neigh2, p2, final=True)

    pspec = jax.tree_util.tree_map(lambda x: P(*([None] * x.ndim)), p1)
    return shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(None, None), P(axes, None), P(axes), pspec, pspec),
        out_specs=P(axes, None),
    )(feats, edges, weights, p1, p2)


def sage_forward_minibatch(params, feats0, feats1, feats2,
                           cfg: GNNConfig) -> jax.Array:
    """Sampled 2-hop forward.

    feats0 (B, F) batch nodes; feats1 (B, f0, F) 1-hop; feats2 (B, f0, f1, F)
    2-hop.  Layer 1 runs on (self=1-hop, neigh=2-hop) and (self=batch,
    neigh=1-hop); layer 2 combines them.
    """
    assert cfg.n_layers == 2
    p1, p2 = params["layers"]
    h1_hop1 = _sage_layer(feats1, feats2.mean(axis=2), p1, final=False)
    h1_self = _sage_layer(feats0, feats1.mean(axis=1), p1, final=False)
    return _sage_layer(h1_self, h1_hop1.mean(axis=1), p2, final=True)


def sage_forward_batched(params, feats, edges, graph_ids, n_graphs,
                         cfg: GNNConfig, ctx: ShardCtx = LOCAL_CTX):
    """Block-diagonal batched small graphs + mean readout -> (G, C)."""
    node_logits = sage_forward_full(params, feats, edges, cfg, ctx)
    summed = jax.ops.segment_sum(node_logits, graph_ids,
                                 num_segments=n_graphs)
    counts = jax.ops.segment_sum(
        jnp.ones((feats.shape[0],), node_logits.dtype), graph_ids,
        num_segments=n_graphs)
    return summed / jnp.maximum(counts, 1.0)[:, None]


def sage_loss(logits, labels, mask=None):
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    per = lse - ll
    if mask is None:
        mask = jnp.ones_like(per)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(per * mask) / n
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / n
    return loss, {"loss": loss, "accuracy": acc}

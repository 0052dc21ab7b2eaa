"""Model building blocks (pure JAX, GSPMD-shardable).

Design notes (see DESIGN.md):
  * Attention is blockwise/flash-style (``lax.scan`` over KV blocks) so the
    score matrix never materialises; activations are sequence-sharded over the
    ``model`` axis during train/prefill, so no head-divisibility constraint.
  * Decode attention is written as plain global ops over a KV cache that is
    sequence-sharded; GSPMD partitions the softmax/contraction reductions
    (verified in the dry-run HLO).
  * MoE is expert-parallel via ``shard_map`` + ``all_to_all`` over the
    ``model`` axis with capacity-bounded, cumsum-slotted dispatch.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import LMConfig
from repro.sharding.spec import Rules, shard_map


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + logical rules threaded through model code (None = local)."""

    mesh: Optional[Mesh] = None
    rules: Rules = Rules()

    def constrain(self, x, *logical):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, self.rules.spec(*logical)))


LOCAL_CTX = ShardCtx()

_NEG_INF = -1e30  # finite mask value: avoids (-inf) - (-inf) = nan paths


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding (NeoX-half style; ``fraction`` < 1 rotates only
# the leading dims of each head — ChatGLM's "2d" RoPE uses fraction=0.5).
# ---------------------------------------------------------------------------

def rope_tables(positions: jax.Array, rotary_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin tables (..., rotary_dim // 2)."""
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               fraction: float = 1.0) -> jax.Array:
    """x: (B, S, H, dh); cos/sin: (B, S, half) or (S, half)."""
    dh = x.shape[-1]
    rotary_dim = int(dh * fraction)
    if rotary_dim % 2:
        rotary_dim -= 1
    half = rotary_dim // 2
    xr, xp = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = xr[..., :half], xr[..., half:]
    # x is (B, S, H, dh); cos/sin come as (S, half) or (B, S, half).
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    elif cos.ndim == 3:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    cos = cos.astype(jnp.float32)
    sin = sin.astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention: lax.scan over KV blocks, f32 running
# (max, sumexp, acc).  Supports GQA broadcast and causal masking at a global
# query offset (used by chunked prefill).
# ---------------------------------------------------------------------------

def _attention_fwd_scan(q, k, v, causal: bool, q_offset: int,
                        block_size: int, scale: float):
    """Streaming flash forward.  Returns (out (B,S,H,dhv) in q.dtype,
    lse (B,Hk,G,S) f32)."""
    B, S, H, dh = q.shape
    T, Hk = k.shape[1], k.shape[2]
    dhv = v.shape[-1]
    G = H // Hk
    bs = min(block_size, T)
    n_blocks = T // bs
    assert n_blocks * bs == T, f"T={T} not divisible by block {bs}"

    qf = (q.astype(jnp.float32) * scale).reshape(B, S, Hk, G, dh)
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, n_blocks, bs, Hk, dh),
                      1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, n_blocks, bs, Hk, dhv),
                      1, 0)
    q_pos = q_offset + jnp.arange(S)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, blk_idx = blk
        # q (B,S,Hk,G,dh)=bskgd, k (B,bs,Hk,dh)=btkd -> scores (B,Hk,G,S,bs)
        s = jnp.einsum("bskgd,btkd->bkgst", qf, kblk,
                       preferred_element_type=jnp.float32)
        if causal:
            k_pos = blk_idx * bs + jnp.arange(bs)
            mask = q_pos[:, None] >= k_pos[None, :]          # (S, bs)
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p, vblk,
                        preferred_element_type=jnp.float32)
        acc_new = acc * jnp.moveaxis(corr, (1, 2, 3), (2, 3, 1))[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hk, G, S), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hk, G, S), jnp.float32)
    a0 = jnp.zeros((B, S, Hk, G, dhv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(n_blocks)))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))             # (B,Hk,G,S)
    l_t = jnp.moveaxis(l, (1, 2, 3), (2, 3, 1))          # (B,S,Hk,G)
    out = acc / jnp.maximum(l_t, 1e-30)[..., None]
    return out.reshape(B, S, H, dhv).astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, q_offset, block_size, scale):
    return _attention_fwd_scan(q, k, v, causal, q_offset, block_size, scale)[0]


def _flash_fwd(q, k, v, causal, q_offset, block_size, scale):
    out, lse = _attention_fwd_scan(q, k, v, causal, q_offset, block_size,
                                   scale)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, q_offset, block_size, scale, res, dout):
    """Flash backward: recompute scores per KV block from the saved
    logsumexp — residuals are O(S), never O(S*T).  (The naive grad-of-scan
    stacks score-sized residuals per block; see EXPERIMENTS.md §Perf.)"""
    q, k, v, out, lse = res
    B, S, H, dh = q.shape
    T, Hk = k.shape[1], k.shape[2]
    dhv = v.shape[-1]
    G = H // Hk
    bs = min(block_size, T)
    n_blocks = T // bs

    qf = q.astype(jnp.float32).reshape(B, S, Hk, G, dh)
    do = dout.astype(jnp.float32).reshape(B, S, Hk, G, dhv)
    of = out.astype(jnp.float32).reshape(B, S, Hk, G, dhv)
    # D = rowsum(dO * O): (B,Hk,G,S)
    delta = jnp.moveaxis(jnp.sum(do * of, -1), (1, 2, 3), (3, 1, 2))
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, n_blocks, bs, Hk, dh),
                      1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, n_blocks, bs, Hk, dhv),
                      1, 0)
    q_pos = q_offset + jnp.arange(S)

    def body(dq_acc, blk):
        kblk, vblk, blk_idx = blk
        s = scale * jnp.einsum("bskgd,btkd->bkgst", qf, kblk,
                               preferred_element_type=jnp.float32)
        if causal:
            k_pos = blk_idx * bs + jnp.arange(bs)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # (B,Hk,G,S,bs)
        dv_b = jnp.einsum("bkgst,bskgd->btkd", p, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bskgd,btkd->bkgst", do, vblk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + scale * jnp.einsum(
            "bkgst,btkd->bskgd", ds, kblk,
            preferred_element_type=jnp.float32)
        dk_b = scale * jnp.einsum("bkgst,bskgd->btkd", ds, qf,
                                  preferred_element_type=jnp.float32)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros((B, S, Hk, G, dh), jnp.float32)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        body, dq0, (kb, vb, jnp.arange(n_blocks)))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(B, T, Hk, dh)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(B, T, Hk, dhv)
    return (dq.reshape(B, S, H, dh).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


_flash_attention.defvjp(_flash_fwd, _flash_bwd)

# Toggle for the §Perf before/after ablation (naive grad-of-scan path).
import os as _os
FLASH_VJP = _os.environ.get("REPRO_FLASH_VJP", "1") == "1"


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True, q_offset: int = 0,
                        block_size: int = 512,
                        scale: Optional[float] = None) -> jax.Array:
    """q: (B,S,H,dh) k/v: (B,T,Hk,dh[v]) -> (B,S,H,dhv).  Flash-style
    streaming forward; custom flash VJP (O(S) residuals) unless FLASH_VJP
    is disabled for ablation."""
    dh = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    if FLASH_VJP:
        return _flash_attention(q, k, v, causal, q_offset, block_size, scale)
    return _attention_fwd_scan(q, k, v, causal, q_offset, block_size,
                               scale)[0]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array, *,
                     scale: Optional[float] = None) -> jax.Array:
    """Single-step decode: q (B,1,H,dh) against a (possibly sequence-sharded)
    KV cache (B,T,Hk,dh).  Written as global ops; GSPMD partitions the
    reductions over the sharded T dim (flash-combine emerges from the
    all-reduce of max/sum/weighted-V).

    The cache is consumed in its own dtype with f32 ACCUMULATION
    (preferred_element_type) — an explicit .astype(f32) would let XLA hoist
    the convert out of the layer scan and carry the whole cache stack in
    f32 (observed: +2.5x HBM on qwen1.5-4b decode_32k; EXPERIMENTS.md §Perf).
    """
    B, _, H, dh = q.shape
    T, Hk = k_cache.shape[1], k_cache.shape[2]
    G = H // Hk
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qf = (q.astype(jnp.float32) * scale).astype(k_cache.dtype) \
        .reshape(B, Hk, G, dh)
    s = jnp.einsum("bkgd,btkd->bkgt", qf, k_cache,
                   preferred_element_type=jnp.float32)
    valid = jnp.arange(T)[None] < cache_len[:, None]          # (B, T)
    s = jnp.where(valid[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)
    return o.reshape(B, 1, H, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def swiglu_ffn(x: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """wi: (D, 2F) fused gate|up; wo: (F, D)."""
    gu = jnp.einsum("bsd,df->bsf", x, wi.astype(x.dtype))
    gate, up = jnp.split(gu, 2, axis=-1)
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, wo.astype(x.dtype))


# ---------------------------------------------------------------------------
# Expert-parallel MoE (shard_map + all_to_all over the ``expert`` axis)
# ---------------------------------------------------------------------------

def _capacity(n_tokens: int, top_k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * factor))
    return max(c, top_k)


def _router(x, router_w, cfg: LMConfig):
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, cfg.moe_top_k)          # (t, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates * cfg.router_scale, eids


def _expert_slots(eids, n_experts: int, cap: int):
    """Rank of each (token, k) pair within its expert (cumsum-slotting)."""
    eid_flat = eids.reshape(-1)                                # (t*k,)
    onehot = jax.nn.one_hot(eid_flat, n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_flat = jnp.take_along_axis(pos, eid_flat[:, None], axis=1)[:, 0]
    return eid_flat, pos_flat


def _expert_ffn(buf, w1, w2, dtype):
    gu = jnp.einsum("ecd,edf->ecf", buf, w1.astype(dtype))
    gate, up = jnp.split(gu, 2, axis=-1)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up,
                      w2.astype(dtype))


def _moe_local_a2a(x, router_w, w1, w2, *, cfg: LMConfig, axis: str,
                   n_shards: int):
    """Sharded-token mode: each device owns distinct tokens; dispatch via
    all_to_all over the expert axis."""
    t, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    cap = _capacity(t, k, E, cfg.capacity_factor)
    gates, eids = _router(x, router_w, cfg)
    eid_flat, pos_flat = _expert_slots(eids, E, cap)
    keep = pos_flat < cap
    slot = jnp.where(keep, eid_flat * cap + pos_flat, E * cap)  # drop bucket
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((E * cap + 1, D), x.dtype).at[slot].add(x[tok_idx])
    buf = buf[:-1].reshape(E, cap, D)
    if n_shards > 1:
        # (E, cap, D) -> (E/p, cap*p, D): route experts to their owner shard.
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)
    y = _expert_ffn(buf, w1, w2, x.dtype)
    if n_shards > 1:
        y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                               tiled=True)                     # back: (E,cap,D)
    y = y.reshape(E * cap, D)
    y = jnp.concatenate([y, jnp.zeros((1, D), y.dtype)], axis=0)
    y_pair = y[slot] * (gates.reshape(-1)[:, None]).astype(y.dtype)
    return jnp.zeros((t, D), y.dtype).at[tok_idx].add(y_pair)


def _moe_local_replicated(x, router_w, w1, w2, *, cfg: LMConfig, axis: str,
                          n_shards: int):
    """Replicated-token mode (decode): every device sees the same tokens,
    computes only its local experts, partial outputs psum'd over the axis."""
    t, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    e_loc = E // n_shards
    # decode path: capacity = t (an expert can receive at most t tokens) —
    # dropping tokens at decode would corrupt generation.
    cap = min(t, _capacity(t, k, E, 1e9))
    gates, eids = _router(x, router_w, cfg)
    eid_flat, pos_flat = _expert_slots(eids, E, cap)
    my = jax.lax.axis_index(axis) if n_shards > 1 else 0
    lo = my * e_loc
    local = (eid_flat >= lo) & (eid_flat < lo + e_loc)
    keep = local & (pos_flat < cap)
    slot = jnp.where(keep, (eid_flat - lo) * cap + pos_flat, e_loc * cap)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e_loc * cap + 1, D), x.dtype).at[slot].add(x[tok_idx])
    y = _expert_ffn(buf[:-1].reshape(e_loc, cap, D), w1, w2, x.dtype)
    y = y.reshape(e_loc * cap, D)
    y = jnp.concatenate([y, jnp.zeros((1, D), y.dtype)], axis=0)
    y_pair = y[slot] * (gates.reshape(-1)[:, None]).astype(y.dtype)
    out = jnp.zeros((t, D), y.dtype).at[tok_idx].add(y_pair)
    if n_shards > 1:
        out = jax.lax.psum(out, axis)
    return out


def moe_block(x: jax.Array, router_w, w1, w2, shared_w1, shared_w2,
              *, cfg: LMConfig, ctx: ShardCtx,
              seq_sharded: bool = True) -> jax.Array:
    """x: (B, S, D).  Experts sharded over the ``expert`` ('model') axis.

    seq_sharded=True (train/prefill): tokens are sequence-sharded over the
    expert axis -> a2a dispatch.  False (decode, S not shardable): tokens
    replicated over the expert axis -> local-expert compute + psum combine.
    """
    B, S, D = x.shape

    if ctx.mesh is None:
        flat = _moe_local_a2a(x.reshape(B * S, D), router_w, w1, w2,
                              cfg=cfg, axis="", n_shards=1)
        out = flat.reshape(B, S, D)
    else:
        r = ctx.rules
        axis = r.expert
        n_shards = ctx.mesh.shape[axis]
        fn = _moe_local_a2a if seq_sharded else _moe_local_replicated
        x_spec = (P(r.batch, r.tensor, None) if seq_sharded
                  else P(r.batch, None, None))

        def body(xl, rwl, w1l, w2l):
            b, s, d = xl.shape
            yl = fn(xl.reshape(b * s, d), rwl, w1l, w2l,
                    cfg=cfg, axis=axis, n_shards=n_shards)
            return yl.reshape(b, s, d)

        out = shard_map(
            body, mesh=ctx.mesh,
            in_specs=(x_spec, P(None, None),
                      P(r.expert, None, None), P(r.expert, None, None)),
            out_specs=x_spec,
        )(x, router_w, w1, w2)

    if shared_w1 is not None:
        out = out + swiglu_ffn(x, shared_w1.astype(x.dtype),
                               shared_w2.astype(x.dtype))
    return out


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): train form expands c_kv; decode uses the absorbed form
# against the compressed cache (c_kv, k_pe) — see DESIGN.md §2.
# ---------------------------------------------------------------------------

def mla_qkv(x, p, cfg: LMConfig, positions):
    """Returns q (B,S,H,qk_dim), k (B,S,H,qk_dim), v (B,S,H,v_dim) and the
    compressed (c_kv, k_pe) pair for cache insertion."""
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd, lr = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    q = jnp.einsum("bsd,dhq->bshq", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = q[..., :nd], q[..., nd:]
    ckr = jnp.einsum("bsd,dc->bsc", x, p["wdkv"].astype(x.dtype))
    c_kv, k_pe = ckr[..., :lr], ckr[..., lr:]
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_tables(positions, rd, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)[:, :, 0]  # shared head
    k_nope = jnp.einsum("bsc,chn->bshn", c_kv, p["wuk"].astype(x.dtype))
    v = jnp.einsum("bsc,chv->bshv", c_kv, p["wuv"].astype(x.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], (B, S, H, rd))], -1)
    qq = jnp.concatenate([q_nope, q_pe], -1)
    return qq, k, v, (c_kv, k_pe)


def mla_decode_absorbed(x, p, cfg: LMConfig, ckv_cache, kpe_cache,
                        cache_len, positions):
    """x: (B,1,D); caches: (B,T,lora) / (B,T,rd) sequence-sharded."""
    B, S, D = x.shape
    H = cfg.n_heads
    nd, rd, vd, lr = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    q = jnp.einsum("bsd,dhq->bshq", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = q[..., :nd], q[..., nd:]
    cos, sin = rope_tables(positions, rd, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    # absorb through W_UK: (B,1,H,nd) x (lora,H,nd) -> (B,1,H,lora).
    # Cache consumed in its own dtype + f32 accumulation (see
    # decode_attention note on convert-hoisting).  XLA-CPU's DotThunk lacks
    # BF16xBF16=F32 for this contraction shape — execute in f32 there
    # (TPU keeps the bf16 MXU path).
    cdt = ckv_cache.dtype
    if jax.default_backend() == "cpu":
        cdt = jnp.float32
    q_t = jnp.einsum("bshn,chn->bshc", q_nope.astype(jnp.float32),
                     p["wuk"].astype(jnp.float32)).astype(cdt)
    scale = 1.0 / math.sqrt(nd + rd)
    s = (jnp.einsum("bshc,btc->bhst", q_t, ckv_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bshr,btr->bhst", q_pe.astype(cdt), kpe_cache,
                      preferred_element_type=jnp.float32)) * scale
    T = ckv_cache.shape[1]
    valid = jnp.arange(T)[None] < cache_len[:, None]
    s = jnp.where(valid[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    pr = jnp.exp(s - m)
    l = jnp.sum(pr, axis=-1, keepdims=True)
    o_c = jnp.einsum("bhst,btc->bshc",
                     (pr / jnp.maximum(l, 1e-30)).astype(cdt), ckv_cache,
                     preferred_element_type=jnp.float32)
    o = jnp.einsum("bshc,chv->bshv", o_c, p["wuv"].astype(jnp.float32))
    return o.astype(x.dtype)                                  # (B,1,H,vd)

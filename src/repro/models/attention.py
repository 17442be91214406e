"""Attention blocks: GQA (global / sliding-window), cross-attention, MLA.

Four execution modes:
  * ``train``   — full sequence, no cache.
  * ``prefill`` — full sequence, returns a populated KV cache.
  * ``chunk``   — chunked prefill: a contiguous token slice written into
                  an existing full-length cache buffer at ``positions``
                  (a scalar offset), attending causally over the buffer
                  prefix. Running a prompt as one chunk is bit-identical
                  to ``prefill`` on the valid region (global attention
                  only — ring-buffer windows are not chunkable here).
  * ``decode``  — one new token against an existing cache.

Decode uses a shard_map'd *distributed* attention: the KV cache is sharded
along the sequence axis over ``ctx.seq_axis`` and each rank computes a
partial softmax that is combined with log-sum-exp weights (flash-decoding
over ICI). With a 1×1 mesh this degenerates to ordinary cached attention,
so CPU smoke tests exercise the identical code path.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MLAConfig
from repro.models.common import (apply_rope, blockwise_attention, dense_init,
                                 naive_attention, rms_norm, init_rms_norm)
from repro.models.mesh_ctx import MeshCtx

Cache = Dict[str, jax.Array]


# ===========================================================================
# GQA attention (global or sliding window)
# ===========================================================================
def attn_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, H, KV, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H, hd), dtype, d),
        "wk": dense_init(ks[1], (d, KV, hd), dtype, d),
        "wv": dense_init(ks[2], (d, KV, hd), dtype, d),
        "wo": dense_init(ks[3], (H, hd, d), dtype, H * hd),
    }


def attn_cache_spec(cfg: ModelConfig, batch: int, max_len: int, window: int,
                    dtype) -> Dict[str, jax.ShapeDtypeStruct]:
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    L = min(max_len, window) if window > 0 else max_len
    return {
        "k": jax.ShapeDtypeStruct((batch, L, KV, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, L, KV, hd), dtype),
    }


def init_attn_cache(cfg, batch, max_len, window, dtype) -> Cache:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        attn_cache_spec(cfg, batch, max_len, window, dtype))


def _project_qkv(params, x, cfg: ModelConfig, positions):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def head_sharded(ctx: MeshCtx, t: jax.Array) -> jax.Array:
    """Pin q/k/v to (batch, seq-replicated, heads-sharded-if-divisible)
    layout before blockwise attention. Without this, a sequence-parallel
    residual stream makes GSPMD re-gather K/V inside EVERY kv-block scan
    iteration (observed ~10× collective inflation on the dry-run)."""
    if ctx.tp_size <= 1:
        return t
    h_ax = ctx.tp_axis if t.shape[2] % ctx.tp_size == 0 else None
    return jax.lax.with_sharding_constraint(
        t, ctx.sharding(ctx.bspec, None, h_ax, None))


def _pad_heads_for_tp(q, k, v, wo, tp: int):
    """§Perf hillclimb A: architectures whose head count does not divide
    the model axis (llama4: 40 heads on 16; recurrentgemma: 10 on 16)
    otherwise run attention fully REPLICATED over TP — 16× the compute
    and score traffic per device. Padding query heads to the next multiple
    of tp (and MHA-izing K/V so grouping stays valid) makes the S² part
    shardable; zero-padded wo rows make the epilogue exact. Cost: +pad/H
    FLOPs and ×G KV traffic — bounded, vs a ×tp saving."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Hp = -(-H // tp) * tp
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    pad = Hp - H
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        wo = jnp.pad(wo, ((0, pad), (0, 0), (0, 0)))
    return q, k, v, wo


def attn_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,                       # [B, S, d]
    *,
    cfg: ModelConfig,
    ctx: MeshCtx,
    mode: str,
    window: int = 0,                    # 0 = global causal
    cache: Optional[Cache] = None,
    positions: Optional[jax.Array] = None,   # [B] decode write positions
) -> Tuple[jax.Array, Optional[Cache]]:
    B, S, d = x.shape
    if mode in ("train", "prefill"):
        pos = jnp.arange(S)
        q, k, v = _project_qkv(params, x, cfg, pos)
        k_cache, v_cache = k, v        # caches keep the un-padded layout
        wo = params["wo"]
        if ctx.tp_size > 1 and q.shape[2] % ctx.tp_size != 0:
            q, k, v, wo = _pad_heads_for_tp(q, k, v, wo, ctx.tp_size)
        q, k, v = head_sharded(ctx, q), head_sharded(ctx, k), head_sharded(ctx, v)
        if S > 2048:
            o = blockwise_attention(q, k, v, causal=True, window=window)
        else:
            o = naive_attention(q, k, v, causal=True, window=window)
        o = head_sharded(ctx, o)
        y = jnp.einsum("bshk,hkd->bsd", o, wo)
        new_cache = None
        if mode == "prefill":
            k, v = k_cache, v_cache
            if window > 0 and S > window:
                # keep the trailing window, aligned to ring slots
                # slot for position p is p % window; trailing window of a
                # prefill of length S covers positions S-window..S-1.
                idx = (jnp.arange(window) +
                       (S - window)) % window
                ksl = jax.lax.dynamic_slice_in_dim(k, S - window, window, 1)
                vsl = jax.lax.dynamic_slice_in_dim(v, S - window, window, 1)
                ck = jnp.zeros_like(ksl).at[:, idx].set(ksl)
                cv = jnp.zeros_like(vsl).at[:, idx].set(vsl)
                new_cache = {"k": ck, "v": cv}
            else:
                new_cache = {"k": k, "v": v}
        return y, new_cache

    if mode == "chunk":
        assert cache is not None and positions is not None
        assert window == 0, "chunked prefill needs global attention"
        return _attn_chunk(params, x, cfg=cfg, ctx=ctx, ref=cache,
                           offset=positions)

    assert mode == "decode" and cache is not None and positions is not None
    q, k_new, v_new = _project_qkv(params, x, cfg,
                                   positions[:, None])  # [B,1,...]
    o, new_cache = decode_attention_distributed(
        q, k_new, v_new, cache, positions, ctx, window=window)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return y, new_cache


def _attn_chunk(params, x, *, cfg: ModelConfig, ctx: MeshCtx, ref,
                offset):
    """Chunked-prefill attention (mode ``chunk``).

    ``x`` is one chunk [B, S, d] of a longer prompt whose earlier chunks
    already populated positions ``< offset`` of the layer's cache buffer
    (a :class:`~repro.models.cache_ref.CacheRef` into the stacked
    carry). The chunk's roped K/V are written at ``offset .. offset+S``
    and queries attend causally over the whole buffer with explicit
    position masks — valid keys sit at the same buffer indices as in a
    monolithic prefill of the same bucketed length, which is what makes
    the chunked result bit-identical on the valid region. TP head
    padding / sharding mirror the monolithic prefill path (the cache
    keeps the un-padded layout)."""
    B, S, d = x.shape
    pos = offset + jnp.arange(S)
    q, k, v = _project_qkv(params, x, cfg, pos)
    kstack, vstack = ref.stack["k"], ref.stack["v"]
    layer = jnp.asarray(ref.idx, jnp.int32)
    start = (layer, jnp.int32(0), jnp.asarray(offset, jnp.int32),
             jnp.int32(0), jnp.int32(0))
    kstack = jax.lax.dynamic_update_slice(
        kstack, k[None].astype(kstack.dtype), start)
    vstack = jax.lax.dynamic_update_slice(
        vstack, v[None].astype(vstack.dtype), start)
    ck = jax.lax.dynamic_index_in_dim(kstack, layer, 0, keepdims=False)
    cv = jax.lax.dynamic_index_in_dim(vstack, layer, 0, keepdims=False)
    wo = params["wo"]
    if ctx.tp_size > 1 and q.shape[2] % ctx.tp_size != 0:
        q, ck, cv, wo = _pad_heads_for_tp(q, ck, cv, wo, ctx.tp_size)
    q, ck, cv = (head_sharded(ctx, q), head_sharded(ctx, ck),
                 head_sharded(ctx, cv))
    o = naive_attention(q, ck, cv, causal=True, q_positions=pos,
                        kv_positions=jnp.arange(ck.shape[1]))
    o = head_sharded(ctx, o)
    y = jnp.einsum("bshk,hkd->bsd", o, wo)
    return y, ref.with_stack({"k": kstack, "v": vstack})


# ---------------------------------------------------------------------------
# Distributed decode attention (flash-decoding over the seq-sharded cache)
# ---------------------------------------------------------------------------
def _local_partial_attention(q, k, v, valid_mask):
    """Partial softmax attention over a local KV shard.

    q: [B, 1, H, hd]; k/v: [B, L, KV, hd]; valid_mask: [B, L] bool.
    Returns (acc [B,1,H,hd] f32 — exp-weighted sum, l [B,1,H] f32 — sum of
    exp, m [B,1,H] f32 — local max logit).
    """
    B, _, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(hd)
    qr = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,blkd->bkgl", qr, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid_mask[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                  # [B,KV,G]
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgl,blkd->bkgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return (acc.reshape(B, 1, H, hd), l.reshape(B, 1, H),
            m.reshape(B, 1, H))


def decode_attention_distributed(
    q: jax.Array,          # [B, 1, H, hd]  (replicated over seq_axis)
    k_new: jax.Array,      # [B, 1, KV, hd]
    v_new: jax.Array,
    ref,                   # CacheRef: k/v stacks [n, B, L, KV, hd]
    positions: jax.Array,  # [B] int32 — position of the NEW token
    ctx: MeshCtx,
    *,
    window: int = 0,
):
    """Insert (k_new, v_new) at ``positions`` in layer ``ref.idx`` of the
    stacked cache and attend over that layer's entries.

    The cache sequence dim is sharded over ``ctx.seq_axis``; each rank
    computes a partial softmax over its shard and partials are combined via
    psum with log-sum-exp weights (flash-decoding over ICI). The write is a
    scatter of just the new token into the stacked carry buffer, so XLA
    keeps the cache in place across the layer scan (per-step write is the
    new token's KV, not a full cache copy). Cache semantics:
      * window == 0: slot for position p is p (cache length == max_len).
      * window  > 0: ring buffer, slot = p % window.
    """
    mesh = ctx.mesh
    seq_ax = ctx.seq_axis if ctx.shard_kv_seq else None
    batch_spec = P(ctx.bspec)

    def inner(q, k_new, v_new, ck_stack, cv_stack, positions, layer):
        r = jax.lax.axis_index(ctx.seq_axis) if seq_ax else 0
        n, B, Lloc, KV, hd = ck_stack.shape
        # global slot of the new token
        slot = positions % window if window > 0 else positions   # [B]
        local_slot = slot - r * Lloc
        owned = (local_slot >= 0) & (local_slot < Lloc)
        safe_slot = jnp.clip(local_slot, 0, Lloc - 1)
        bidx = jnp.arange(B)
        # scatter just the new token into the stacked carry (in place)
        k_upd = jnp.where(owned[:, None, None], k_new[:, 0],
                          ck_stack[layer, bidx, safe_slot])
        v_upd = jnp.where(owned[:, None, None], v_new[:, 0],
                          cv_stack[layer, bidx, safe_slot])
        ck_stack = ck_stack.at[layer, bidx, safe_slot].set(k_upd)
        cv_stack = cv_stack.at[layer, bidx, safe_slot].set(v_upd)
        ck = jax.lax.dynamic_index_in_dim(ck_stack, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_stack, layer, 0, keepdims=False)
        # positions stored in each local slot
        slots = jnp.arange(Lloc) + r * Lloc                      # [Lloc]
        if window > 0:
            # ring: slot s holds position p ≡ s (mod window), p ≤ pos
            delta = (positions[:, None] - slots[None, :]) % window
            kv_pos = positions[:, None] - delta                  # [B, Lloc]
            valid = (kv_pos >= 0) & (kv_pos >= positions[:, None] - window + 1)
            valid &= kv_pos <= positions[:, None]
        else:
            kv_pos = jnp.broadcast_to(slots[None, :], (B, Lloc))
            valid = kv_pos <= positions[:, None]
        acc, l, m = _local_partial_attention(q, ck, cv, valid)
        if seq_ax:
            gm = jax.lax.pmax(m, seq_ax)
            w = jnp.exp(m - gm)
            acc = jax.lax.psum(acc * w[..., None], seq_ax)
            l = jax.lax.psum(l * w, seq_ax)
        out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        return out, ck_stack, cv_stack

    cache_spec = P(None, ctx.bspec, seq_ax, None, None)
    out, nk, nv = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(ctx.bspec, None, None, None),
                  P(ctx.bspec, None, None, None),
                  P(ctx.bspec, None, None, None),
                  cache_spec, cache_spec, batch_spec, P()),
        out_specs=(P(ctx.bspec, None, None, None),
                   cache_spec, cache_spec),
        check_vma=False,
    )(q, k_new, v_new, ref.stack["k"], ref.stack["v"], positions,
      jnp.asarray(ref.idx, jnp.int32))
    return out, ref.with_stack({"k": nk, "v": nv})


# ===========================================================================
# Cross attention (VLM image layers / enc-dec decoder)
# ===========================================================================
def cross_attn_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d, H, KV, hd = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                    cfg.resolved_head_dim)
    ks = jax.random.split(key, 4)
    md = cfg.encoder_d_model or cfg.d_model
    return {
        "wq": dense_init(ks[0], (d, H, hd), dtype, d),
        "wk": dense_init(ks[1], (md, KV, hd), dtype, md),
        "wv": dense_init(ks[2], (md, KV, hd), dtype, md),
        "wo": dense_init(ks[3], (H, hd, d), dtype, H * hd),
    }


def cross_attn_cache_spec(cfg: ModelConfig, batch: int, mem_len: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, mem_len, KV, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, mem_len, KV, hd), dtype),
    }


def cross_attn_apply(
    params, x, *, cfg: ModelConfig, ctx: MeshCtx, mode: str,
    memory: Optional[jax.Array] = None,       # [B, M, md] (train/prefill)
    cache: Optional[Cache] = None,
) -> Tuple[jax.Array, Optional[Cache]]:
    if mode in ("train", "prefill"):
        assert memory is not None
        k = jnp.einsum("bmd,dhk->bmhk", memory, params["wk"])
        v = jnp.einsum("bmd,dhk->bmhk", memory, params["wv"])
        new_cache = {"k": k, "v": v} if mode == "prefill" else None
    else:
        assert cache is not None
        if hasattr(cache, "read"):          # CacheRef (decode in scan)
            k, v = cache.read("k"), cache.read("v")
        else:
            k, v = cache["k"], cache["v"]
        new_cache = cache                    # read-only in decode
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    o = naive_attention(q, k, v, causal=False)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return y, new_cache


# ===========================================================================
# MLA — DeepSeek multi-head latent attention
# ===========================================================================
def mla_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    m: MLAConfig = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank), dtype, d),
        "q_norm": init_rms_norm(m.q_lora_rank),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H,
                                   m.qk_nope_head_dim + m.qk_rope_head_dim),
                           dtype, m.q_lora_rank),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype, d),
        "kv_norm": init_rms_norm(m.kv_lora_rank),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                           dtype, m.kv_lora_rank),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim),
                           dtype, m.kv_lora_rank),
        "wo": dense_init(ks[5], (H, m.v_head_dim, d), dtype,
                         H * m.v_head_dim),
    }


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "ckv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim),
                                      dtype),
    }


def _mla_qkv_latent(params, x, cfg, positions):
    """Shared: q (nope+rope) and latent kv (ckv, krope)."""
    m = cfg.mla
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, params["wq_a"]),
                  params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, params["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv = rms_norm(kv[..., : m.kv_lora_rank], params["kv_norm"], cfg.norm_eps)
    krope = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                       cfg.rope_theta)[:, :, 0]                 # [B,S,rope]
    return q_nope, q_rope, ckv, krope


def mla_apply(
    params, x, *, cfg: ModelConfig, ctx: MeshCtx, mode: str,
    cache: Optional[Cache] = None, positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Cache]]:
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    if mode in ("train", "prefill"):
        pos = jnp.arange(S)
        q_nope, q_rope, ckv, krope = _mla_qkv_latent(params, x, cfg, pos)
        # naive (expanded) attention: per-head k = [k_nope ; k_rope]
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, params["wk_b"])
        v = jnp.einsum("bsr,rhk->bshk", ckv, params["wv_b"])
        q = jnp.concatenate(
            [q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None],
                                      (B, S, H, m.qk_rope_head_dim))],
            axis=-1)
        q, k, v = (head_sharded(ctx, q), head_sharded(ctx, k),
                   head_sharded(ctx, v))
        if S > 2048:
            o = blockwise_attention(q, k, v)
        else:
            o = naive_attention(q, k, v)
        o = head_sharded(ctx, o)
        y = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
        new_cache = {"ckv": ckv, "krope": krope} if mode == "prefill" else None
        return y, new_cache

    if mode == "chunk":
        assert cache is not None and positions is not None
        return _mla_chunk(params, x, cfg=cfg, ctx=ctx, ref=cache,
                          offset=positions)

    assert mode == "decode" and cache is not None and positions is not None
    q_nope, q_rope, ckv_new, krope_new = _mla_qkv_latent(
        params, x, cfg, positions[:, None])
    # absorbed: q' = q_nope @ wk_b^T  → latent space scores against ckv
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wk_b"])
    o_lat, new_cache = _mla_decode_distributed(
        q_lat, q_rope, ckv_new, krope_new, cache, positions, ctx,
        scale_dim=m.qk_nope_head_dim + m.qk_rope_head_dim)
    # o_lat: [B,1,H,r] → expand through wv_b
    o = jnp.einsum("bshr,rhk->bshk", o_lat, params["wv_b"])
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return y, new_cache


def _mla_chunk(params, x, *, cfg: ModelConfig, ctx: MeshCtx, ref, offset):
    """Chunked-prefill MLA (mode ``chunk``): write the chunk's latent
    (ckv, krope) into the cache buffer at ``offset``, then expand
    per-head K/V from the WHOLE buffer (same expansion the monolithic
    prefill applies per position) and attend with explicit position
    masks — bit-identical to one-shot prefill on the valid region."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    pos = offset + jnp.arange(S)
    q_nope, q_rope, ckv, krope = _mla_qkv_latent(params, x, cfg, pos)
    ckv_stack, krope_stack = ref.stack["ckv"], ref.stack["krope"]
    layer = jnp.asarray(ref.idx, jnp.int32)
    start = (layer, jnp.int32(0), jnp.asarray(offset, jnp.int32),
             jnp.int32(0))
    ckv_stack = jax.lax.dynamic_update_slice(
        ckv_stack, ckv[None].astype(ckv_stack.dtype), start)
    krope_stack = jax.lax.dynamic_update_slice(
        krope_stack, krope[None].astype(krope_stack.dtype), start)
    ckv_all = jax.lax.dynamic_index_in_dim(ckv_stack, layer, 0,
                                           keepdims=False)
    krope_all = jax.lax.dynamic_index_in_dim(krope_stack, layer, 0,
                                             keepdims=False)
    L = ckv_all.shape[1]
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_all, params["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", ckv_all, params["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_all[:, :, None],
                                  (B, L, H, m.qk_rope_head_dim))],
        axis=-1)
    q, k, v = (head_sharded(ctx, q), head_sharded(ctx, k),
               head_sharded(ctx, v))
    o = naive_attention(q, k, v, q_positions=pos,
                        kv_positions=jnp.arange(L))
    o = head_sharded(ctx, o)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return y, ref.with_stack({"ckv": ckv_stack, "krope": krope_stack})


def _mla_decode_distributed(q_lat, q_rope, ckv_new, krope_new, ref,
                            positions, ctx: MeshCtx, scale_dim: int):
    """Flash-decoding over the seq-sharded latent cache (stacked carry)."""
    mesh = ctx.mesh
    seq_ax = ctx.seq_axis if ctx.shard_kv_seq else None
    scale = 1.0 / np.sqrt(scale_dim)

    def inner(q_lat, q_rope, ckv_new, krope_new, ckv_stack, krope_stack,
              positions, layer):
        r = jax.lax.axis_index(ctx.seq_axis) if seq_ax else 0
        n, B, Lloc, R = ckv_stack.shape
        local_slot = positions - r * Lloc
        owned = (local_slot >= 0) & (local_slot < Lloc)
        safe = jnp.clip(local_slot, 0, Lloc - 1)
        bidx = jnp.arange(B)
        ckv_upd = jnp.where(owned[:, None], ckv_new[:, 0],
                            ckv_stack[layer, bidx, safe])
        krope_upd = jnp.where(owned[:, None], krope_new[:, 0],
                              krope_stack[layer, bidx, safe])
        ckv_stack = ckv_stack.at[layer, bidx, safe].set(ckv_upd)
        krope_stack = krope_stack.at[layer, bidx, safe].set(krope_upd)
        ckv = jax.lax.dynamic_index_in_dim(ckv_stack, layer, 0,
                                           keepdims=False)
        krope = jax.lax.dynamic_index_in_dim(krope_stack, layer, 0,
                                             keepdims=False)
        kv_pos = jnp.arange(Lloc)[None, :] + r * Lloc
        valid = kv_pos <= positions[:, None]
        s = (jnp.einsum("bhr,blr->bhl", q_lat[:, 0], ckv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhk,blk->bhl", q_rope[:, 0], krope,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(valid[:, None, :], s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bhl,blr->bhr", p.astype(ckv.dtype), ckv,
                         preferred_element_type=jnp.float32)
        if seq_ax:
            gm = jax.lax.pmax(m, seq_ax)
            w = jnp.exp(m - gm)
            acc = jax.lax.psum(acc * w[..., None], seq_ax)
            l = jax.lax.psum(l * w, seq_ax)
        out = (acc / jnp.maximum(l[..., None], 1e-30))[:, None]  # [B,1,H,r]
        return out.astype(q_lat.dtype), ckv_stack, krope_stack

    bspec = P(ctx.bspec)
    c_spec = P(None, ctx.bspec, seq_ax, None)
    out, nckv, nkrope = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(ctx.bspec, None, None, None),
                  P(ctx.bspec, None, None, None),
                  P(ctx.bspec, None, None),
                  P(ctx.bspec, None, None),
                  c_spec, c_spec, bspec, P()),
        out_specs=(P(ctx.bspec, None, None, None), c_spec, c_spec),
        check_vma=False,
    )(q_lat, q_rope, ckv_new, krope_new, ref.stack["ckv"],
      ref.stack["krope"], positions, jnp.asarray(ref.idx, jnp.int32))
    return out, ref.with_stack({"ckv": nckv, "krope": nkrope})

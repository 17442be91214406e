"""Composable transformer assembly for all six architecture families.

A model is a stack of *blocks*, each block = (sequence mixer, FFN) with
pre-norms and residual connections. The stack is split into:

  * ``prefix``  — explicit leading blocks (e.g. DeepSeek's dense layers),
  * ``blocks``  — N repetitions of ``cfg.layer_pattern`` ("superblocks"),
                  parameters stacked on a leading axis and executed with
                  ``lax.scan`` (compile-time stays flat in depth),
  * ``tail``    — pattern remainder, unrolled (e.g. RecurrentGemma 26 = 3·8+2).

Encoder-decoder models (seamless-m4t) add an ``encoder`` stack whose output
is the ``memory`` consumed by CROSS_ATTN blocks. VLMs receive ``memory``
directly (stubbed vision frontend per the assignment carve-out).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, CROSS_ATTN, LOCAL_ATTN, MLA_ATTN, MLP,
                                MOE, NONE, RGLRU, SSM, ModelConfig)
from repro.models import attention as A
from repro.models import cache_ref
from repro.models import ffn as F
from repro.models import rglru as R
from repro.models import ssm as S
from repro.models.common import (chunked_softmax_xent, dtype_of, embed_init,
                                 init_rms_norm, rms_norm)
from repro.models.mesh_ctx import MeshCtx

PyTree = Any


# ===========================================================================
# Single block
# ===========================================================================
def block_init(key, cfg: ModelConfig, kind: Tuple[str, str], dtype) -> PyTree:
    mixer, ffn = kind
    k1, k2 = jax.random.split(key)
    p: Dict[str, PyTree] = {"mixer_norm": init_rms_norm(cfg.d_model)}
    if mixer in (ATTN, LOCAL_ATTN):
        p["mixer"] = A.attn_init(k1, cfg, dtype)
    elif mixer == CROSS_ATTN:
        p["mixer"] = A.cross_attn_init(k1, cfg, dtype)
    elif mixer == MLA_ATTN:
        p["mixer"] = A.mla_init(k1, cfg, dtype)
    elif mixer == RGLRU:
        p["mixer"] = R.rglru_init(k1, cfg, dtype)
    elif mixer == SSM:
        p["mixer"] = S.ssm_init(k1, cfg, dtype)
    else:
        raise ValueError(mixer)
    if ffn == MLP:
        p["ffn_norm"] = init_rms_norm(cfg.d_model)
        p["ffn"] = F.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == MOE:
        p["ffn_norm"] = init_rms_norm(cfg.d_model)
        p["ffn"] = F.moe_init(k2, cfg, dtype)
    return p


def block_cache_spec(cfg: ModelConfig, kind, batch: int, max_len: int,
                     mem_len: int, dtype, window_override: int = 0):
    """ShapeDtypeStruct pytree for one block's decode cache (or None)."""
    mixer, _ = kind
    if mixer == ATTN:
        return A.attn_cache_spec(cfg, batch, max_len, window_override, dtype)
    if mixer == LOCAL_ATTN:
        w = cfg.sliding_window or cfg.rglru.window
        return A.attn_cache_spec(cfg, batch, max_len, w, dtype)
    if mixer == CROSS_ATTN:
        return A.cross_attn_cache_spec(cfg, batch, mem_len, dtype)
    if mixer == MLA_ATTN:
        return A.mla_cache_spec(cfg, batch, max_len, dtype)
    if mixer == RGLRU:
        return R.rglru_cache_spec(cfg, batch, dtype)
    if mixer == SSM:
        return S.ssm_cache_spec(cfg, batch, dtype)
    raise ValueError(mixer)


#: mixers named ``attention`` in the device programs' op metadata
_ATTENTION = (ATTN, LOCAL_ATTN, CROSS_ATTN, MLA_ATTN)


def block_apply(params, x, *, cfg: ModelConfig, ctx: MeshCtx, kind,
                mode: str, cache=None, positions=None, memory=None,
                window_override: int = 0, placement=None):
    """Returns (x_out, new_cache, expert_counts[E] or zeros[1]).

    ``placement``: this layer's EPLB slice ``(replica_slots, n_replicas,
    phys_owner)`` from a :class:`~repro.serving.eplb.PlacementTable`
    (decode path; ``None`` ⇒ logical expert routing)."""
    mixer, ffn = kind
    with jax.named_scope("attention" if mixer in _ATTENTION else mixer):
        h = rms_norm(x, params["mixer_norm"], cfg.norm_eps)
        if mixer == ATTN:
            y, new_cache = A.attn_apply(
                params["mixer"], h, cfg=cfg, ctx=ctx, mode=mode,
                window=window_override, cache=cache, positions=positions)
        elif mixer == LOCAL_ATTN:
            w = cfg.sliding_window or cfg.rglru.window
            y, new_cache = A.attn_apply(
                params["mixer"], h, cfg=cfg, ctx=ctx, mode=mode, window=w,
                cache=cache, positions=positions)
        elif mixer == CROSS_ATTN:
            y, new_cache = A.cross_attn_apply(
                params["mixer"], h, cfg=cfg, ctx=ctx, mode=mode,
                memory=memory, cache=cache)
        elif mixer == MLA_ATTN:
            y, new_cache = A.mla_apply(params["mixer"], h, cfg=cfg,
                                       ctx=ctx, mode=mode, cache=cache,
                                       positions=positions)
        elif mixer == RGLRU:
            y, new_cache = R.rglru_apply(params["mixer"], h, cfg=cfg,
                                         ctx=ctx, mode=mode, cache=cache)
        elif mixer == SSM:
            y, new_cache = S.ssm_apply(params["mixer"], h, cfg=cfg,
                                       ctx=ctx, mode=mode, cache=cache)
        else:
            raise ValueError(mixer)
    x = x + y

    counts = jnp.zeros((cfg.moe.num_experts or 1,), jnp.float32)
    aux = jnp.zeros((2,), jnp.float32)
    if ffn == MLP:
        with jax.named_scope("mlp"):
            h = rms_norm(x, params["ffn_norm"], cfg.norm_eps)
            x = x + F.mlp_apply(params["ffn"], h)
    elif ffn == MOE:
        h = rms_norm(x, params["ffn_norm"], cfg.norm_eps)
        y, moe_aux = F.moe_apply(params["ffn"], h, cfg=cfg, ctx=ctx,
                                 mode=mode, placement=placement)
        x = x + y
        counts = moe_aux["expert_counts"]
        aux = jnp.stack([moe_aux["moe_lb_loss"], moe_aux["moe_z_loss"]])
    return x, new_cache, (aux, counts)


# ===========================================================================
# Model
# ===========================================================================
class Model:
    """Functional model wrapper. All methods are pure and jit-friendly."""

    def __init__(self, cfg: ModelConfig, ctx: MeshCtx,
                 long_context: bool = False):
        self.cfg = cfg
        self.ctx = ctx
        self.dtype = dtype_of(cfg.dtype)
        # long-context serving substitutes a sliding window for global
        # attention (dense archs only; see DESIGN.md §4)
        self.window_override = (cfg.long_context_window
                                if long_context and not
                                cfg.supports_long_context else 0)
        kinds = cfg.layer_kinds()
        np_, nsb, pl = len(cfg.prefix_layers), cfg.num_superblocks, cfg.pattern_len
        self.prefix_kinds = kinds[:np_]
        self.pattern = cfg.layer_pattern
        self.n_sb = nsb
        self.tail_kinds = kinds[np_ + nsb * pl:]

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> PyTree:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(key, 8)
        params: Dict[str, PyTree] = {
            "embed": embed_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                dtype),
            "final_norm": init_rms_norm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(keys[1],
                                           (cfg.d_model, cfg.vocab_size),
                                           dtype)
        if self.prefix_kinds:
            pk = jax.random.split(keys[2], len(self.prefix_kinds))
            params["prefix"] = tuple(
                block_init(k, cfg, kind, dtype)
                for k, kind in zip(pk, self.prefix_kinds))
        if self.n_sb:
            def init_sb(k):
                ks = jax.random.split(k, len(self.pattern))
                return {f"pos{i}": block_init(ks[i], cfg, kind, dtype)
                        for i, kind in enumerate(self.pattern)}
            sb_keys = jax.random.split(keys[3], self.n_sb)
            params["blocks"] = jax.vmap(init_sb)(sb_keys)
        if self.tail_kinds:
            tk = jax.random.split(keys[4], len(self.tail_kinds))
            params["tail"] = tuple(
                block_init(k, cfg, kind, dtype)
                for k, kind in zip(tk, self.tail_kinds))
        if cfg.is_encdec:
            params["encoder"] = self._encoder_init(keys[5])
        if cfg.mtp_num_layers:
            mk = jax.random.split(keys[6], cfg.mtp_num_layers)
            params["mtp"] = tuple(self._mtp_init(k) for k in mk)
        return params

    def _encoder_init(self, key):
        cfg = self.cfg
        ecfg = dataclasses.replace(
            cfg, d_model=cfg.encoder_d_model or cfg.d_model,
            prefix_layers=(), layer_pattern=((ATTN, MLP),),
            num_layers=cfg.encoder_layers)
        ks = jax.random.split(key, cfg.encoder_layers + 1)
        return {
            "blocks": tuple(block_init(k, ecfg, (ATTN, MLP), self.dtype)
                            for k in ks[:-1]),
            "norm": init_rms_norm(ecfg.d_model),
        }

    def _mtp_init(self, key):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        from repro.models.common import dense_init
        return {
            "proj": dense_init(k1, (2 * cfg.d_model, cfg.d_model),
                               self.dtype, 2 * cfg.d_model),
            "norm_h": init_rms_norm(cfg.d_model),
            "norm_e": init_rms_norm(cfg.d_model),
            "block": block_init(k2, cfg, (self.pattern[-1][0], MLP)
                                if self.pattern[-1][0] != CROSS_ATTN
                                else (ATTN, MLP), self.dtype),
        }

    # ------------------------------------------------------------------
    # caches
    # ------------------------------------------------------------------
    def cache_spec(self, batch: int, max_len: int,
                   mem_len: Optional[int] = None) -> PyTree:
        cfg = self.cfg
        mem_len = mem_len or cfg.num_frontend_tokens
        mk = functools.partial(block_cache_spec, cfg, batch=batch,
                               max_len=max_len, mem_len=mem_len,
                               dtype=self.dtype,
                               window_override=self.window_override)
        spec: Dict[str, PyTree] = {}
        if self.prefix_kinds:
            spec["prefix"] = tuple(mk(kind=k) for k in self.prefix_kinds)
        if self.n_sb:
            def stack(s):
                return jax.ShapeDtypeStruct((self.n_sb,) + s.shape, s.dtype)
            spec["blocks"] = {
                f"pos{i}": jax.tree.map(stack, mk(kind=kind))
                for i, kind in enumerate(self.pattern)}
        if self.tail_kinds:
            spec["tail"] = tuple(mk(kind=k) for k in self.tail_kinds)
        return spec

    def init_cache(self, batch: int, max_len: int,
                   mem_len: Optional[int] = None) -> PyTree:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_spec(batch, max_len, mem_len))

    def mtp_cache_spec(self, batch: int, max_len: int) -> PyTree:
        """Batched decode-state spec for the MTP draft head (§4.6):
        ``"kv"`` — the head's block decode cache (same shapes
        :meth:`mtp_step` writes through the ``CacheRef`` machinery, all
        leaves batch-major like the main cache's single blocks), and
        ``"hidden"`` — the ``[B, 1, d]`` main-model final hidden carried
        across decode iterations as the head's conditioning input."""
        cfg = self.cfg
        kind = (self.pattern[-1][0], MLP)
        if kind[0] == CROSS_ATTN:
            kind = (ATTN, MLP)
        # window_override defaults to 0 to match mtp_step's block_apply
        return {
            "kv": block_cache_spec(cfg, kind, batch, max_len,
                                   cfg.num_frontend_tokens, self.dtype),
            "hidden": jax.ShapeDtypeStruct((batch, 1, cfg.d_model),
                                           self.dtype),
        }

    def init_mtp_cache(self, batch: int, max_len: int) -> PyTree:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.mtp_cache_spec(batch, max_len))

    # ------------------------------------------------------------------
    # core stack application
    # ------------------------------------------------------------------
    def _apply_stack(self, params, x, *, mode, caches=None, positions=None,
                     memory=None, placement=None):
        cfg, ctx = self.cfg, self.ctx
        apply = functools.partial(block_apply, cfg=cfg, ctx=ctx, mode=mode,
                                  positions=positions, memory=memory,
                                  window_override=self.window_override)
        new_caches: Dict[str, PyTree] = {}
        aux_sum = jnp.zeros((2,), jnp.float32)
        counts_list: List[jax.Array] = []
        np_, pl_len = len(self.prefix_kinds), len(self.pattern)

        def layer_placement(layer_idx: int):
            """Per-layer EPLB slice at a PYTHON layer index (prefix/tail
            unrolled sections; the scan slices its own xs)."""
            if placement is None:
                return None
            return placement.layer(layer_idx)

        def get(c, key, i):
            return None if c is None or key not in c else c[key][i]

        def run_unrolled(section, i, kind, x):
            c = get(caches, section, i)
            gl = i if section == "prefix" \
                else np_ + self.n_sb * pl_len + i
            lp = layer_placement(gl)
            if mode in ("decode", "chunk") and c is not None:
                ref = cache_ref.wrap_single(c)
                x, nref, (aux, counts) = apply(params[section][i], x,
                                               kind=kind, cache=ref,
                                               placement=lp)
                nc = cache_ref.unwrap_single(nref)
            else:
                x, nc, (aux, counts) = apply(params[section][i], x,
                                             kind=kind, cache=c,
                                             placement=lp)
            new_caches.setdefault(section, []).append(nc)
            return x, aux, counts

        for i, kind in enumerate(self.prefix_kinds):
            x, aux, counts = run_unrolled("prefix", i, kind, x)
            aux_sum += aux
            counts_list.append(counts)

        # superblock placement slices rearranged [n_sb, pattern_len, ...]
        # and scanned as xs next to the stacked params
        pl_blocks = None
        if placement is not None and self.n_sb:
            sl = slice(np_, np_ + self.n_sb * pl_len)
            pl_blocks = tuple(
                a[sl].reshape((self.n_sb, pl_len) + a.shape[1:])
                for a in (placement.replica_slots, placement.n_replicas,
                          placement.phys_owner))

        if self.n_sb and mode in ("decode", "chunk"):
            # caches are carried (not scanned xs/ys) so that the per-step
            # cache write is an in-place scatter of the new token only
            # (decode) or of the current chunk's slice (chunked prefill).
            def superblock_dec(carry, xs):
                x, aux_acc, cstacks = carry
                sb_params, idx, sb_pl = xs
                cts = []
                for i, kind in enumerate(self.pattern):
                    ref = cache_ref.CacheRef(cstacks[f"pos{i}"], idx)
                    lp = None if sb_pl is None \
                        else tuple(a[i] for a in sb_pl)
                    x, nref, (aux, counts) = apply(sb_params[f"pos{i}"], x,
                                                   kind=kind, cache=ref,
                                                   placement=lp)
                    cstacks = dict(cstacks)
                    cstacks[f"pos{i}"] = nref.stack
                    aux_acc = aux_acc + aux
                    cts.append(counts)
                return (x, aux_acc, cstacks), jnp.stack(cts)

            (x, aux_sum, nc_stack), counts_sb = jax.lax.scan(
                superblock_dec, (x, aux_sum, caches["blocks"]),
                (params["blocks"], jnp.arange(self.n_sb), pl_blocks))
            new_caches["blocks"] = nc_stack
            counts_list.append(counts_sb.sum(axis=(0, 1)))
        elif self.n_sb:
            def superblock(carry, xs):
                x, aux_acc = carry
                sb_params = xs
                ncs = {}
                cts = []
                for i, kind in enumerate(self.pattern):
                    x, nc, (aux, counts) = apply(sb_params[f"pos{i}"], x,
                                                 kind=kind, cache=None)
                    ncs[f"pos{i}"] = nc
                    cts.append(counts)
                    aux_acc = aux_acc + aux
                # drop None cache entries for scan-compatibility
                ncs = {k: v for k, v in ncs.items() if v is not None}
                return (x, aux_acc), (ncs if ncs else None,
                                      jnp.stack(cts))

            body = superblock
            if ctx.remat == "full":
                body = jax.checkpoint(superblock)
            (x, aux_sum), (nc_stack, counts_sb) = jax.lax.scan(
                body, (x, aux_sum), params["blocks"])
            if nc_stack is not None:
                new_caches["blocks"] = nc_stack
            counts_list.append(counts_sb.sum(axis=(0, 1)))

        for i, kind in enumerate(self.tail_kinds):
            x, aux, counts = run_unrolled("tail", i, kind, x)
            aux_sum += aux
            counts_list.append(counts)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        for k in ("prefix", "tail"):
            if k in new_caches:
                new_caches[k] = tuple(new_caches[k])
        counts = (jnp.sum(jnp.stack(
            [c for c in counts_list if c.shape[0] > 1]), axis=0)
            if cfg.has_moe else jnp.zeros((1,), jnp.float32))
        return x, new_caches, aux_sum, counts

    # ------------------------------------------------------------------
    # encoder (audio)
    # ------------------------------------------------------------------
    def encode(self, params, frames: jax.Array) -> jax.Array:
        """frames: [B, M, d_enc] stubbed frontend embeddings → memory."""
        cfg = self.cfg
        ecfg = dataclasses.replace(
            cfg, d_model=cfg.encoder_d_model or cfg.d_model)
        x = frames
        for bp in params["encoder"]["blocks"]:
            h = rms_norm(x, bp["mixer_norm"], cfg.norm_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, bp["mixer"]["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, bp["mixer"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, bp["mixer"]["wv"])
            from repro.models.common import naive_attention
            o = naive_attention(q, k, v, causal=False)
            x = x + jnp.einsum("bshk,hkd->bsd", o, bp["mixer"]["wo"])
            h = rms_norm(x, bp["ffn_norm"], cfg.norm_eps)
            x = x + F.mlp_apply(bp["ffn"], h)
        return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)

    # ------------------------------------------------------------------
    # public steps
    # ------------------------------------------------------------------
    def _unembed(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _embed(self, params, tokens):
        x = params["embed"][tokens]
        return x.astype(self.dtype)

    def _residual_constraint(self, x, mode):
        ctx = self.ctx
        if mode in ("train", "prefill") and x.shape[1] % max(
                ctx.axis_size(ctx.tp_axis), 1) == 0 and ctx.tp_size > 1:
            # sequence-parallel residual stream
            return jax.lax.with_sharding_constraint(
                x, ctx.sharding(ctx.bspec, ctx.tp_axis, None))
        return x

    def forward_train(self, params, tokens, labels, memory=None,
                      loss_mask=None):
        """tokens/labels: [B, S]. Returns (loss, metrics)."""
        if self.cfg.is_encdec:
            memory = self.encode(params, memory)
        x = self._embed(params, tokens)
        x = self._residual_constraint(x, "train")
        x, _, aux, counts = self._apply_stack(params, x, mode="train",
                                              memory=memory)
        nll, n_tok = chunked_softmax_xent(x, labels, self._unembed(params),
                                          mask=loss_mask)
        loss = nll + aux[0] + aux[1]
        metrics = {"nll": nll, "moe_lb_loss": aux[0], "moe_z_loss": aux[1],
                   "tokens": n_tok, "expert_counts": counts}
        return loss, metrics

    def prefill(self, params, tokens, memory=None, last_pos=None):
        """tokens: [B, S] → (logits at ``last_pos`` (default S-1) [B, V],
        cache). ``last_pos`` supports right-padded serving batches."""
        if self.cfg.is_encdec:
            memory = self.encode(params, memory)
        x = self._embed(params, tokens)
        x = self._residual_constraint(x, "prefill")
        x, caches, _, _ = self._apply_stack(params, x, mode="prefill",
                                            memory=memory)
        if last_pos is None:
            h = x[:, -1]
        else:
            h = x[jnp.arange(x.shape[0]), last_pos]
        logits = jnp.einsum("bd,dv->bv", h.astype(jnp.float32),
                            self._unembed(params).astype(jnp.float32))
        return logits, caches

    def prefill_chunk(self, params, cache, tokens, offset, last_pos):
        """Chunked prefill: run ONE contiguous chunk of a prompt against
        the partially-filled cache buffers in ``cache``.

        ``tokens``: [B, S_chunk] (padded chunk); ``offset``: scalar int32
        absolute position of the chunk's first token (earlier chunks
        populated positions ``< offset``); ``last_pos``: [B] index WITHIN
        the chunk of its last valid token. Returns ``(logits [B, V] at
        the last valid position, new cache)`` — on the final chunk these
        logits equal :meth:`prefill`'s, and the cache's valid region
        (positions ``< prompt_len``) is bit-identical to the monolithic
        prefill cache of the same bucketed length. ``cache`` is the
        full-length buffer pytree from :meth:`init_cache` (batch 1 in
        serving). Global-attention mixers only (ATTN / MLA_ATTN)."""
        assert not self.cfg.is_encdec, "chunked prefill: decoder-only"
        x = self._embed(params, tokens)
        x = self._residual_constraint(x, "prefill")
        x, caches, _, _ = self._apply_stack(params, x, mode="chunk",
                                            caches=cache,
                                            positions=offset)
        with jax.named_scope("output_head"):
            h = x[jnp.arange(x.shape[0]), last_pos]
            logits = jnp.einsum("bd,dv->bv", h.astype(jnp.float32),
                                self._unembed(params).astype(jnp.float32))
        return logits, caches

    def decode_step(self, params, cache, tokens, positions, memory=None,
                    placement=None):
        """tokens: [B, 1]; positions: [B]. → (logits [B, V], new cache).

        ``placement``: optional device-resident
        :class:`~repro.serving.eplb.PlacementTable` (leading dim =
        n_layers) — the EPLB data plane each MoE layer routes through."""
        logits, _, new_caches = self.decode_step_hidden(
            params, cache, tokens, positions, memory=memory,
            placement=placement)
        return logits, new_caches

    def decode_step_hidden(self, params, cache, tokens, positions,
                           memory=None, placement=None):
        """:meth:`decode_step` that also returns the final hidden state
        ``[B, 1, d]`` — the MTP draft head's conditioning input. Runs the
        IDENTICAL op sequence as ``decode_step`` (which delegates here),
        so logits stay bit-identical between the two entry points."""
        x = self._embed(params, tokens)
        x, new_caches, _, _ = self._apply_stack(params, x, mode="decode",
                                                caches=cache,
                                                positions=positions,
                                                memory=memory,
                                                placement=placement)
        with jax.named_scope("output_head"):
            logits = jnp.einsum("bd,dv->bv", x[:, -1].astype(jnp.float32),
                                self._unembed(params).astype(jnp.float32))
        return logits, x[:, -1:], new_caches

    # ------------------------------------------------------------------
    # MTP draft head (paper §4.6): h' = Block(proj([norm(h); norm(e_next)]))
    # ------------------------------------------------------------------
    def mtp_step(self, params, mtp_index: int, hidden, next_tokens,
                 positions, mtp_cache=None):
        """hidden: [B,1,d] main-model final hidden; next_tokens: [B,1].
        Returns (draft logits [B,V], new hidden [B,1,d], cache)."""
        cfg = self.cfg
        mp = params["mtp"][mtp_index]
        e = self._embed(params, next_tokens)
        h = jnp.concatenate([
            rms_norm(hidden, mp["norm_h"], cfg.norm_eps),
            rms_norm(e, mp["norm_e"], cfg.norm_eps)], axis=-1)
        h = jnp.einsum("bsd,de->bse", h, mp["proj"])
        kind = (self.pattern[-1][0], MLP)
        if kind[0] == CROSS_ATTN:
            kind = (ATTN, MLP)
        if mtp_cache is not None:
            ref = cache_ref.wrap_single(mtp_cache)
            h, nref, _ = block_apply(mp["block"], h, cfg=cfg, ctx=self.ctx,
                                     kind=kind, mode="decode",
                                     cache=ref, positions=positions)
            nc = cache_ref.unwrap_single(nref)
        else:
            h, nc, _ = block_apply(mp["block"], h, cfg=cfg, ctx=self.ctx,
                                   kind=kind, mode="train",
                                   cache=None, positions=positions)
        logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                            self._unembed(params).astype(jnp.float32))
        return logits, h, nc


def build_model(cfg: ModelConfig, ctx: MeshCtx,
                long_context: bool = False) -> Model:
    return Model(cfg, ctx, long_context=long_context)

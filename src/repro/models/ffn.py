"""FFN blocks: dense MLP and Mixture-of-Experts.

The MoE layer implements the paper's two communication regimes as two
interchangeable execution strategies (both inside ``shard_map`` so the same
code runs on the 1×1 smoke mesh and on 256/512-device meshes):

* ``alltoall`` — XCCL ``dispatch``/``combine`` (§3.2): tokens are
  sequence-sharded over the EP axis; each rank packs per-destination-rank
  capacity buffers, `lax.all_to_all` routes them, local experts compute via
  a capacity-padded grouped matmul, and a reverse all_to_all + weighted sum
  combines. Used for train/prefill.

* ``gather`` — the pull-based dispatch over global shared memory (§3.1/§3.2
  "pull" protocol): tokens are *replicated* over the EP axis (the shared-
  memory analogue), each rank gathers the tokens routed to its local
  experts, computes, and a psum acts as combine. Used for decode, where the
  token count per step is small — this is exactly the regime where the
  paper's memory-semantic pull beats a scatter protocol.

Shared experts (DeepSeek-MoE / DeepSeek-V3 / Llama-4) run as a dense MLP
outside the routed path.

EPLB placement (§4.5): ``moe_apply`` optionally takes a per-layer
``placement`` — ``(replica_slots [E, R], n_replicas [E], phys_owner
[n_phys])`` sliced from the device-resident
:class:`~repro.serving.eplb.PlacementTable` — and the decode gather
strategy then routes each token assignment to a *physical replica slot*
(round-robin of token position across the logical expert's replicas).
With no redundancy (budget 0) this is bit-identical to logical routing;
with redundancy, a hot expert's load genuinely splits across its
replica buckets. The slot buckets run through the owner-indexed
grouped matmul (``kernels/gmm.placement_gmm``): the grid step for slot
``s`` scalar-prefetches ``phys_owner[s]`` and streams the owner's
weight blocks straight from HBM, so replica slots are just extra
grouped-matmul rows — no per-step owner-gathered ``[n_phys, d, f]``
weight materialization (``placement_gather_free=False`` keeps the
legacy gathered path as a benchmark baseline).

Placement covers BOTH decode gather regimes. Replicated experts route
every slot locally. Under sharded EP, physical slots are block-sharded
over the EP ranks (slot ``s`` lives on rank ``s // (n_phys//ep_size)``)
and the ``mine`` mask comes from *slot ownership* instead of the
logical ``flat_idx // E_local`` test — a hot expert's replicas land on
different ranks and split its load across the pod, with the psum
combine unchanged. Expert weights stay logically indexed and
replicated over the EP axis in that path (the §3.1 UB global-shared-
memory analogue: any rank streams any owner's blocks), which trades
weight memory for gather-free replica routing exactly like the paper's
pull-based decode dispatch.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig
from repro.kernels.gmm.ops import expert_ffn
from repro.kernels.route_pack.ops import (fused_route_pack,
                                          placement_route,
                                          placement_route_local)
from repro.models.common import dense_init, microbatch_sizes
from repro.models.mesh_ctx import MeshCtx


# ===========================================================================
# Dense MLP (SwiGLU)
# ===========================================================================
def mlp_init(key, d: int, f: int, dtype) -> Dict[str, jax.Array]:
    ks = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(ks[0], (d, f), dtype, d),
        "wi_up": dense_init(ks[1], (d, f), dtype, d),
        "wo": dense_init(ks[2], (f, d), dtype, f),
    }


def mlp_apply(params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"])
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, params["wo"])


# ===========================================================================
# MoE
# ===========================================================================
def moe_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    d = cfg.d_model
    e: MoEConfig = cfg.moe
    ks = jax.random.split(key, 5)
    params = {
        "router": dense_init(ks[0], (d, e.num_experts), jnp.float32, d),
        "we_gate": dense_init(ks[1], (e.num_experts, d, e.expert_d_ff),
                              dtype, d),
        "we_up": dense_init(ks[2], (e.num_experts, d, e.expert_d_ff),
                            dtype, d),
        "we_down": dense_init(ks[3], (e.num_experts, e.expert_d_ff, d),
                              dtype, e.expert_d_ff),
    }
    if e.num_shared_experts:
        f_sh = (e.shared_d_ff or e.expert_d_ff) * e.num_shared_experts
        params["shared"] = mlp_init(ks[4], d, f_sh, dtype)
    return params


# ---------------------------------------------------------------------------
# Capacity machinery: both strategies pack buckets through the fused
# route-pack op (kernels/route_pack — capacity rank + quantize + scatter
# in one pass); the reference capacity_rank/scatter_to_buckets semantics
# live in xccl/routing.py, validated bit-identical in the test suite.
# ---------------------------------------------------------------------------
def _route(x_flat: jax.Array, router_w: jax.Array, top_k: int):
    """Returns (expert idx [T,k], weights [T,k] f32, probs [T,E] f32,
    logits [T,E] f32)."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return idx, w, probs, logits


def _expert_ffn(params_slice, buckets: jax.Array, *, owner=None,
                use_pallas=None) -> jax.Array:
    """buckets: [E_local, C, d] → [E_local, C, d] (capacity-padded GMM,
    ``kernels/gmm`` — fused Pallas kernel off-CPU, jnp oracle on CPU).

    ``owner`` [n_slots] int32 switches to the owner-indexed placement
    GMM: slot ``s`` computes against ``params[owner[s]]``'s weight
    blocks streamed straight from HBM (replica slots are extra grouped-
    matmul rows; no owner-gathered weight materialization). The Pallas
    paths carry no VJP — train callers pass ``use_pallas=False``."""
    out = expert_ffn(buckets, params_slice["we_gate"],
                     params_slice["we_up"], params_slice["we_down"],
                     phys_owner=owner, use_pallas=use_pallas)
    return out.astype(buckets.dtype)


def _aux_stats(probs, idx, n_experts: int, logits):
    """Load-balance + router-z losses (Switch-style)."""
    k = idx.shape[-1]
    # fraction of assignments per expert
    counts = jnp.sum(jax.nn.one_hot(idx, n_experts, dtype=jnp.float32),
                     axis=(0, 1))
    f = counts / jnp.maximum(jnp.sum(counts), 1.0)
    p = jnp.mean(probs, axis=0)
    lb = n_experts * jnp.sum(f * p)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return lb, z, counts


# ---------------------------------------------------------------------------
# Strategy 1: all_to_all dispatch/combine (XCCL §3.2)
# ---------------------------------------------------------------------------
def _moe_alltoall_local(x, params, cfg: ModelConfig, ep_axis: str,
                        ep_size: int, all_axes: Tuple[str, ...],
                        train: bool):
    """Per-shard body. x: [B_l, S_l, d], sequence sharded over ep_axis.
    Requires num_experts % ep_size == 0 and ep_size > 1."""
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    k = e.top_k
    E = e.num_experts
    E_local = E // ep_size

    xf = x.reshape(T, d)
    idx, w, probs, logits = _route(xf, params["router"], k)
    lb, z, counts = _aux_stats(probs, idx, E, logits)

    N = T * k
    flat_idx = idx.reshape(N)
    flat_w = w.reshape(N)
    tok_of = jnp.repeat(jnp.arange(T), k)

    # ---- stage 1: pack per-destination-rank capacity buffers -------------
    # fused route-pack: capacity rank + bucket scatter in one streaming
    # pass (the top-k payload repeat happens inside the kernel, never as
    # a materialized [N, d] gather)
    dest_rank = flat_idx // E_local
    cap_s = max(int(N / ep_size * e.capacity_factor), 4)
    pack1 = fused_route_pack(xf, dest_rank, eid=flat_idx % E_local, k=k,
                             n_dest=ep_size, capacity=cap_s)
    send_tok, send_eid = pack1.buckets, pack1.eids       # [R,C,d], [R,C]
    rank1, keep1 = pack1.rank, pack1.keep
    # ---- dispatch (all_to_all over the EP axis) ---------------------------
    recv_tok = jax.lax.all_to_all(send_tok, ep_axis, 0, 0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axis, 0, 0, tiled=True)
    # ---- local expert compute (capacity-padded grouped matmul) ------------
    flat_tok = recv_tok.reshape(ep_size * cap_s, d)
    flat_eid = recv_eid.reshape(ep_size * cap_s)
    valid = flat_eid >= 0
    cap_e = max(int(ep_size * cap_s / E_local * e.capacity_factor), 4)
    # empty receive rows go to the no-slot destination E_local: parked on
    # a real expert they would use up its capacity and drop its tokens
    pack2 = fused_route_pack(flat_tok, jnp.where(valid, flat_eid, E_local),
                             valid=valid, n_dest=E_local, capacity=cap_e)
    buckets = pack2.buckets
    rank2, keep2 = pack2.rank, pack2.keep
    local_params = {
        n: params[n] for n in ("we_gate", "we_up", "we_down")
    }
    out_b = _expert_ffn(local_params, buckets,
                        use_pallas=False if train else None)
    y_flat = out_b[jnp.where(valid, flat_eid, 0),
                   jnp.clip(rank2, 0, cap_e - 1)]
    y_flat = jnp.where(keep2[:, None], y_flat, 0.0).astype(x.dtype)
    # ---- combine (reverse all_to_all + weighted sum) -----------------------
    back = jax.lax.all_to_all(y_flat.reshape(ep_size, cap_s, d),
                              ep_axis, 0, 0, tiled=True)           # [R,C,d]
    y_assign = back[dest_rank, jnp.clip(rank1, 0, cap_s - 1)]
    y_assign = jnp.where(keep1[:, None], y_assign, 0.0)
    y = jnp.zeros((T, d), x.dtype).at[tok_of].add(
        (y_assign * flat_w[:, None]).astype(x.dtype))
    # every shard holds distinct tokens → reduce over batch AND ep axes
    lb = jax.lax.pmean(lb, all_axes)
    z = jax.lax.pmean(z, all_axes)
    counts = jax.lax.psum(counts, all_axes)
    return y.reshape(B, S, d), (lb, z, counts)


# ---------------------------------------------------------------------------
# Strategy 2: pull-based gather-compute-reduce (decode)
# ---------------------------------------------------------------------------
def _moe_gather_local(x, params, cfg: ModelConfig, ep_axes,
                      ep_size: int, batch_axes: Tuple[str, ...],
                      mesh_shape: Dict[str, int], train: bool,
                      microbatches: int = 1, placement=None,
                      gather_free: bool = True):
    """x: [B_l, S, d]. Each rank pulls the tokens routed to its local
    experts and psum combines (the pull-based dispatch analogue).

    ``ep_axes`` may be a single axis name or a TUPLE spanning the batch
    axes (the paper's EP-per-die layout, e.g. 256 experts over a 16×16
    pod): then the token batch is first all-gathered over the overlapping
    axes (A2E — tokens fan in to the expert dies) and the local batch
    shard is sliced back after the psum combine (E2A).

    ``ep_size`` is the *effective* EP degree: 1 when experts are
    replicated (indivisible expert count or 1×1 mesh).

    ``microbatches >= 2`` is the §4.4 decode ping-pong: the batch is
    split and each micro-batch runs the full gather→GMM→combine chain
    independently, issued back to back so the A2E/E2A collectives of one
    micro-batch overlap the expert GMM of the other under XLA's async
    collective scheduling (aux stats become token-weighted averages).

    ``placement`` activates EPLB physical-slot routing: buckets are per
    *physical slot* — replicas included — and the expert GMM is owner-
    indexed (``kernels/gmm.placement_gmm`` streams each slot's owner
    weights; ``gather_free=False`` keeps the legacy owner-gathered
    baseline). Rotation position is the flattened token index within
    the (micro-)batch, so replica selection needs no communication.
    Under sharded EP the physical slots are block-sharded over the EP
    ranks and ``mine`` is the slot-ownership mask (weights arrive
    replicated in that path — see ``moe_apply``)."""
    e = cfg.moe
    if isinstance(ep_axes, str):
        ep_axes = (ep_axes,)
    replicated_experts = ep_size == 1
    overlap = tuple(a for a in ep_axes if a in batch_axes) \
        if not replicated_experts else ()

    def run(x):
        """Full gather-compute-reduce for one (micro-)batch [B, S, d]."""
        B, S, d = x.shape
        if overlap:
            for a in overlap:          # A2E: fan tokens in to expert dies
                x = jax.lax.all_gather(x, a, axis=0, tiled=True)
        T = x.shape[0] * S
        k = e.top_k
        E = e.num_experts
        E_local = E if replicated_experts else E // ep_size

        xf = x.reshape(T, d)
        idx, w, probs, logits = _route(xf, params["router"], k)
        lb, z, counts = _aux_stats(probs, idx, E, logits)

        N = T * k
        flat_idx = idx.reshape(N)
        flat_w = w.reshape(N)
        tok_of = jnp.repeat(jnp.arange(T), k)

        owner_arg = None
        if placement is not None:
            # EPLB physical-slot indirection: replica selected by
            # round-robin of the token index (§4.5 step 4); buckets are
            # per physical slot and the GMM is owner-indexed — slot s
            # streams params[owner[s]]'s blocks in-kernel instead of
            # materializing owner-gathered weights
            rep_slots, n_rep, owner = placement
            n_phys = owner.shape[0]
            if replicated_experts:
                my_eid = placement_route(flat_idx, tok_of, rep_slots,
                                         n_rep)
                mine = jnp.ones((N,), bool)
                n_slots = n_phys
                owner_local = owner
            else:
                # sharded-EP placement: slots block-sharded over the EP
                # ranks, `mine` from SLOT ownership — a hot expert's
                # replicas land on different ranks and split its load
                r = jnp.int32(0)
                for a in ep_axes:
                    r = r * mesh_shape[a] + jax.lax.axis_index(a)
                n_slots = n_phys // ep_size
                my_eid, mine = placement_route_local(
                    flat_idx, tok_of, rep_slots, n_rep, r, n_slots)
                owner_local = jax.lax.dynamic_slice_in_dim(
                    owner, r * n_slots, n_slots)
            # capacity uses the LOGICAL expected load N/E (a slot's
            # round-robin share never exceeds its owner's full load),
            # with the same sharded-skew margin as logical routing —
            # budget 0 stays bit-identical to the non-placement path
            cap = max(int(N / E * e.capacity_factor
                          * (1 if replicated_experts else 4)), 4)
            if gather_free:
                ffn_params, owner_arg = params, owner_local
            else:       # legacy owner-gathered weights (bench baseline)
                ffn_params = {n: params[n][owner_local]
                              for n in ("we_gate", "we_up", "we_down")}
        else:
            if replicated_experts:
                my_eid, mine = flat_idx, jnp.ones((N,), bool)
            else:
                r = jnp.int32(0)
                for a in ep_axes:
                    r = r * mesh_shape[a] + jax.lax.axis_index(a)
                mine = (flat_idx // E_local) == r
                my_eid = flat_idx % E_local
            # expected assignments PER EXPERT = N/E (buckets are per
            # expert); a 4× skew margin covers routing imbalance in the
            # sharded case (EPLB keeps the tail bounded)
            n_slots = E_local
            cap = max(int(N / E * e.capacity_factor
                          * (1 if replicated_experts else 4)), 4)
            ffn_params = params
        # assignments owned by other EP ranks take no slot here (dest
        # n_slots), so they cannot use up a local expert's capacity
        pack = fused_route_pack(xf, jnp.where(mine, my_eid, n_slots),
                                valid=mine, k=k, n_dest=n_slots,
                                capacity=cap)
        rank, keep = pack.rank, pack.keep
        out_b = _expert_ffn(ffn_params, pack.buckets, owner=owner_arg,
                            use_pallas=False if train else None)
        y_assign = out_b[jnp.where(mine, my_eid, 0),
                         jnp.clip(rank, 0, cap - 1)]
        y_assign = jnp.where(keep[:, None], y_assign, 0.0)
        y = jnp.zeros((T, d), jnp.float32).at[tok_of].add(
            y_assign.astype(jnp.float32) * flat_w[:, None])
        if not replicated_experts:
            y = jax.lax.psum(y, ep_axes)        # combine (E2A analogue)
        if overlap:
            # E2A slice-back: keep only this rank's batch shard
            ro = jnp.int32(0)
            for a in overlap:
                ro = ro * mesh_shape[a] + jax.lax.axis_index(a)
            y = jax.lax.dynamic_slice_in_dim(
                y.reshape(-1, S, d), ro * B, B, axis=0).reshape(B * S, d)
        return y.reshape(B, S, d), (lb, z, counts)

    B = x.shape[0]
    sizes = microbatch_sizes(B, microbatches)
    if len(sizes) == 1:
        y, (lb, z, counts) = run(x)
    else:
        chunks, off = [], 0
        for sz in sizes:
            chunks.append(x[off:off + sz])
            off += sz
        outs = [run(c) for c in chunks]
        y = jnp.concatenate([o[0] for o in outs], axis=0)
        wts = jnp.asarray([float(sz) / B for sz in sizes], jnp.float32)
        lb = sum(o[1][0] * wt for o, wt in zip(outs, wts))
        z = sum(o[1][1] * wt for o, wt in zip(outs, wts))
        counts = sum(o[1][2] for o in outs)
    # stats: reduce over batch axes not already covered by the EP gather
    stat_axes = tuple(a for a in batch_axes if a not in overlap)
    if stat_axes:
        lb = jax.lax.pmean(lb, stat_axes)
        z = jax.lax.pmean(z, stat_axes)
        counts = jax.lax.psum(counts, stat_axes)
    return y.astype(x.dtype), (lb, z, counts)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------
def moe_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,                   # [B, S, d]
    *,
    cfg: ModelConfig,
    ctx: MeshCtx,
    mode: str,                      # train | prefill | decode
    placement=None,                 # per-layer (replica_slots, n_replicas,
                                    # phys_owner) from a PlacementTable
    placement_gather_free: bool = True,   # False: legacy owner-gathered
                                          # weights (benchmark baseline)
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    e = cfg.moe
    impl = "gather" if mode == "decode" else ctx.moe_impl
    ep_axis = ctx.ep_axis            # str, or tuple for EP-per-die layout
    ep_tuple = (ep_axis,) if isinstance(ep_axis, str) else tuple(ep_axis)
    ep_size = ctx.axis_size(ep_axis)
    mesh = ctx.mesh
    train = mode == "train"

    routed = {n: params[n] for n in ("router", "we_gate", "we_up", "we_down")}
    # expert weights are sharded over the EP axis (dim 0) unless indivisible
    ep_ok = e.num_experts % ep_size == 0 and ep_size > 1
    seq_ok = x.shape[1] % ep_size == 0 and ep_size > 1
    w_entry = (ep_tuple if len(ep_tuple) > 1 else ep_tuple[0]) \
        if ep_ok else None
    w_spec = {n: P(w_entry) for n in ("we_gate", "we_up", "we_down")}
    w_spec["router"] = P()
    eff_ep = ep_size if ep_ok else 1
    all_axes = tuple(ctx.batch_axes) + tuple(
        a for a in ep_tuple if a not in ctx.batch_axes)

    if impl == "alltoall" and ep_ok and seq_ok and len(ep_tuple) == 1:
        x_spec = P(ctx.bspec, ep_tuple[0], None)
        body = functools.partial(_moe_alltoall_local, cfg=cfg,
                                 ep_axis=ep_tuple[0], ep_size=eff_ep,
                                 all_axes=all_axes, train=train)
        placement = None          # EPLB placement is a decode-path plane
    else:
        # pull-based gather-compute-reduce (also the 1×1-mesh degenerate)
        x_spec = P(ctx.bspec, None, None)
        body = functools.partial(_moe_gather_local, cfg=cfg,
                                 ep_axes=ep_axis, ep_size=eff_ep,
                                 batch_axes=tuple(ctx.batch_axes),
                                 mesh_shape=dict(ctx.mesh.shape),
                                 train=train,
                                 microbatches=(ctx.decode_microbatches
                                               if mode == "decode" else 1),
                                 gather_free=placement_gather_free)
        if eff_ep != 1 and placement is not None:
            # sharded-EP placement: physical slots block-shard over the
            # EP ranks. Pad the owner view to a multiple of eff_ep with
            # dead identity slots (replica_slots can never reference
            # them, so they stay empty GMM rows), and replicate the
            # expert weights over the EP axis — the §3.1 UB global-
            # shared-memory analogue: any rank streams any owner's
            # blocks; the psum combine is unchanged.
            rs_, nr_, owner_ = (jnp.asarray(a) for a in placement)
            pad = (-owner_.shape[0]) % eff_ep
            if pad:
                ext = (jnp.arange(owner_.shape[0],
                                  owner_.shape[0] + pad, dtype=owner_.dtype)
                       % e.num_experts)
                owner_ = jnp.concatenate([owner_, ext])
            placement = (rs_, nr_, owner_)
            w_spec = {n: P() for n in ("router", "we_gate", "we_up",
                                       "we_down")}

    if placement is not None:
        pl = tuple(jnp.asarray(a) for a in placement)
        gather_body = body

        def body_with_placement(x, w, p):
            return gather_body(x, w, placement=p)

        y, (lb, z, counts) = jax.shard_map(
            body_with_placement, mesh=mesh,
            in_specs=(x_spec, w_spec, (P(), P(), P())),
            out_specs=(x_spec, (P(), P(), P())),
            check_vma=False,
        )(x, routed, pl)
    else:
        y, (lb, z, counts) = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, w_spec),
            out_specs=(x_spec, (P(), P(), P())),
            check_vma=False,
        )(x, routed)

    if "shared" in params:
        y = y + mlp_apply(params["shared"], x)

    aux = {
        "moe_lb_loss": lb * e.router_aux_coef,
        "moe_z_loss": z * e.router_z_coef,
        "expert_counts": counts,
    }
    return y, aux

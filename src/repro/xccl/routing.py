"""XCCL expert-parallel collectives: dispatch / combine / A2E / E2A.

Executable (shard_map) implementations of the paper's all-to-all layer:

* ``dispatch``/``combine`` (§3.2) — colocated MoE-Attention expert
  parallelism: capacity-bucketed ``lax.all_to_all`` over the EP axis with
  optional fused INT8 quantization of the payload (§4.7 "communication
  quantization": quantize before the wire, dequantize after).

* ``a2e``/``e2a`` (§3.3) — disaggregated MoE-Attention with asymmetric
  rank counts. Ranks [0, n_attn) are attention, [0, n_expert) host experts
  (the first ``n_attn`` expert ranks double as *trampolines*). A2E routes
  token payloads attention→trampoline with a collective_permute
  (point-to-point, one peer per attention rank — this is what keeps the
  metadata fan-out O(1) instead of O(n_expert)), then trampolines fan out
  to all expert ranks with an all_to_all. E2A reverses the two stages.

The models' MoE layer (models/ffn.py) uses the same capacity machinery;
these standalone ops are used by core/moe_attn_disagg.py, the serving
engine, tests, and benchmarks.

The packing stages route through ``kernels/route_pack`` — capacity rank
+ INT8 quantize + bucket scatter fused into one streaming pass (Pallas
off-CPU, a bit-identical jnp oracle on CPU). ``capacity_rank`` /
``scatter_to_buckets`` below remain the reference semantics the kernel
is validated against (tests/test_properties.py).

EPLB physical-slot indirection (§4.5): when a device-resident
``PlacementTable`` is active, destinations entering the pack are
*physical replica slots*, not logical expert ids — the remap is
:func:`placement_route` (re-exported here; round-robin of token
position across a logical expert's replicas, a pure gather with no
cross-NPU coordination). With no redundancy the remap is the identity
bit-for-bit, so all reference semantics below are unchanged.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.route_pack.ops import fused_route_pack, placement_route

__all__ = [
    "capacity_rank", "scatter_to_buckets", "quantize_tokens",
    "dequantize_tokens", "placement_route", "DispatchResult",
    "dispatch_local", "combine_local", "a2e_local", "e2a_local",
    "make_a2e_e2a",
]


# ---------------------------------------------------------------------------
# Capacity machinery (re-exported; models/ffn.py shares it)
# ---------------------------------------------------------------------------
def capacity_rank(dest: jax.Array, n_dest: int, capacity: int):
    """dest: [N] int32 in [0, n_dest). FIFO rank within each destination +
    keep mask (rank < capacity)."""
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    my_rank = jnp.take_along_axis(ranks, dest[:, None], axis=1)[:, 0]
    return my_rank, my_rank < capacity


def scatter_to_buckets(values, dest, rank, keep, n_dest, capacity, fill=0):
    safe_rank = jnp.where(keep, rank, capacity)
    buf = jnp.full((n_dest, capacity + 1) + values.shape[1:], fill,
                   values.dtype)
    buf = buf.at[dest, safe_rank].set(values, mode="drop")
    return buf[:, :capacity]


# ---------------------------------------------------------------------------
# Fused INT8 communication quantization (§3.2 step 2, §4.7)
# ---------------------------------------------------------------------------
def quantize_tokens(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Token-wise INT8: x [..., d] → (int8 values, f32 scale per token)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def dequantize_tokens(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


class DispatchResult(NamedTuple):
    tokens: jax.Array          # [E_local, C_e, d] bucketed expert inputs
    meta_eid: jax.Array        # bookkeeping to reverse the routing
    meta_rank2: jax.Array
    meta_keep2: jax.Array
    dest_rank: jax.Array       # per-assignment stage-1 routing
    rank1: jax.Array
    keep1: jax.Array
    tok_of: jax.Array
    weights: jax.Array


def _pack_stage1(xf, flat_idx, ep_size, e_local, cap_s, quantize):
    """Bucket assignments by destination EP rank — one fused route-pack
    pass (capacity rank + INT8 quantize + bucket scatter; the Pallas
    kernel off-CPU, its bit-identical jnp oracle on CPU)."""
    dest_rank = flat_idx // e_local
    pack = fused_route_pack(xf, dest_rank, eid=flat_idx % e_local,
                            n_dest=ep_size, capacity=cap_s,
                            quantize=quantize)
    return (pack.buckets, pack.scales, pack.eids, dest_rank, pack.rank,
            pack.keep)


def dispatch_local(x_assign, flat_idx, *, ep_axis: str, ep_size: int,
                   n_experts: int, capacity_factor: float = 1.25,
                   quantize: bool = True):
    """Per-shard dispatch body (inside shard_map).

    x_assign: [N, d] payload per assignment (token repeated per top-k);
    flat_idx: [N] global expert ids. Returns (expert_buckets [E_l, C_e, d]
    f32, routing state for combine).
    """
    n, d = x_assign.shape
    e_local = n_experts // ep_size
    cap_s = max(int(n / ep_size * capacity_factor), 4)
    send_tok, send_sc, send_eid, dest_rank, rank1, keep1 = _pack_stage1(
        x_assign, flat_idx, ep_size, e_local, cap_s, quantize)
    # ---- the wire (all_to_all over EP ranks) --------------------------
    recv_tok = jax.lax.all_to_all(send_tok, ep_axis, 0, 0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axis, 0, 0, tiled=True)
    if quantize:
        recv_sc = jax.lax.all_to_all(send_sc, ep_axis, 0, 0, tiled=True)
        flat = dequantize_tokens(recv_tok.reshape(-1, d),
                                 recv_sc.reshape(-1))
    else:
        flat = recv_tok.reshape(-1, d).astype(jnp.float32)
    flat_eid = recv_eid.reshape(-1)
    valid = flat_eid >= 0
    cap_e = max(int(flat.shape[0] / e_local * capacity_factor), 4)
    # empty receive rows take no slot (dest e_local)
    pack2 = fused_route_pack(flat, jnp.where(valid, flat_eid, e_local),
                             valid=valid, n_dest=e_local, capacity=cap_e)
    buckets, rank2, keep2 = pack2.buckets, pack2.rank, pack2.keep
    state = (flat_eid, rank2, keep2, dest_rank, rank1, keep1, cap_s, cap_e)
    return buckets, state


def combine_local(expert_out, state, *, ep_axis: str, ep_size: int,
                  quantize: bool = True):
    """Reverse routing: expert buckets → per-assignment outputs [N, d]."""
    flat_eid, rank2, keep2, dest_rank, rank1, keep1, cap_s, cap_e = state
    d = expert_out.shape[-1]
    y_flat = expert_out[jnp.where(flat_eid >= 0, flat_eid, 0),
                        jnp.clip(rank2, 0, cap_e - 1)]
    y_flat = jnp.where(keep2[:, None], y_flat, 0.0)
    if quantize:
        qv, sc = quantize_tokens(y_flat)
        back_q = jax.lax.all_to_all(qv.reshape(ep_size, cap_s, d),
                                    ep_axis, 0, 0, tiled=True)
        back_s = jax.lax.all_to_all(sc.reshape(ep_size, cap_s),
                                    ep_axis, 0, 0, tiled=True)
        back = dequantize_tokens(back_q.reshape(-1, d), back_s.reshape(-1))
        back = back.reshape(ep_size, cap_s, d)
    else:
        back = jax.lax.all_to_all(
            y_flat.astype(jnp.float32).reshape(ep_size, cap_s, d),
            ep_axis, 0, 0, tiled=True)
    y_assign = back[dest_rank, jnp.clip(rank1, 0, cap_s - 1)]
    return jnp.where(keep1[:, None], y_assign, 0.0)


# ---------------------------------------------------------------------------
# A2E / E2A with trampoline forward (§3.3)
# ---------------------------------------------------------------------------
def a2e_local(payload, *, role_axis: str, n_attn: int, n_expert: int):
    """Stage the attention→expert routing with trampoline forward.

    Runs inside shard_map over ``role_axis`` with n_attn + 0 shared ranks:
    the mesh axis has ``n_expert`` ranks; ranks < n_attn are ALSO attention
    ranks (colocated simulation of the disaggregated deployment — on real
    hardware these are distinct dies; the dataflow is identical).

    payload: [n_expert, C, d] per-source-rank buckets destined to each
    expert rank (zeros on pure-expert ranks).
    Stage 1 (A2E): attention rank a sends its full buffer to trampoline
    rank a (identity collective_permute — point-to-point, metadata O(1)).
    Stage 2 (A2E'): trampolines all_to_all the per-destination buckets to
    all expert ranks.
    """
    # stage 1: attention → trampoline (perm: a → a for a < n_attn)
    perm = [(a, a) for a in range(n_attn)]
    staged = jax.lax.ppermute(payload, role_axis, perm)
    # stage 2: trampolines → experts
    return jax.lax.all_to_all(staged, role_axis, 0, 0, tiled=True)


def e2a_local(payload, *, role_axis: str, n_attn: int, n_expert: int):
    """Expert → attention: experts all_to_all to trampolines (E2A'), then
    trampolines forward to attention ranks (E2A)."""
    staged = jax.lax.all_to_all(payload, role_axis, 0, 0, tiled=True)
    perm = [(a, a) for a in range(n_attn)]
    return jax.lax.ppermute(staged, role_axis, perm)


def make_a2e_e2a(mesh: Mesh, role_axis: str, n_attn: int, n_expert: int):
    """shard_map-wrapped A2E/E2A over a 1-axis mesh of n_expert ranks."""
    spec = P(role_axis, None, None, None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def a2e(x):
        return a2e_local(x[0], role_axis=role_axis, n_attn=n_attn,
                         n_expert=n_expert)[None]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    def e2a(x):
        return e2a_local(x[0], role_axis=role_axis, n_attn=n_attn,
                         n_expert=n_expert)[None]

    return a2e, e2a

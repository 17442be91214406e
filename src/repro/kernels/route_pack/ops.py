"""jit'd wrapper: pads the assignment dim, exposes use_pallas switch.

``use_pallas=None`` (default) picks the execution automatically: the
compiled Pallas kernel off-CPU, the equivalent jnp oracle on CPU
(where the interpreter would only add overhead inside jitted serving
steps). Tests pin ``use_pallas=True`` to validate the kernel in
interpret mode against the oracle bit-for-bit.

The pass also provides EPLB *physical-slot indirection*:
:func:`placement_route` remaps destinations logical→physical-replica-
slot by round-robin of token position; callers (``models/ffn.py``,
``core/moe_attn_disagg.py``) apply it to their routed ids before the
rank/quantize/scatter pass, so redundant experts (§4.5) split their
load across capacity buckets and the remap gather fuses into the same
jitted program as the pack itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.route_pack.kernel import route_pack_kernel
from repro.kernels.route_pack.ref import (RoutePack, quantize_rows,
                                          route_pack_ref, scatter_rows)
from repro.kernels.runtime import on_cpu, resolve_interpret

# assignments per grid step: the SMEM block of dest/valid/rank/keep (XLA
# tiles 1-D int32 arrays by 1024, so a multi-block split uses 1024)
_BLOCK = 1024


@functools.partial(jax.jit, static_argnames=("k", "n_dest", "capacity",
                                             "quantize", "use_pallas",
                                             "interpret"))
def _dispatch(x, dest, valid, eid, *, k, n_dest, capacity, quantize,
              use_pallas, interpret):
    if not use_pallas:
        return route_pack_ref(x, dest, valid, eid, k=k, n_dest=n_dest,
                              capacity=capacity, quantize=quantize)
    N = dest.shape[0]
    bn = min(_BLOCK, -(-N // 128) * 128)
    pad_n = (-N) % bn
    if valid is None:
        valid = jnp.ones((N,), jnp.int32)
    dest_p = jnp.concatenate(
        [dest.astype(jnp.int32), jnp.full((pad_n,), n_dest, jnp.int32)])
    valid_p = jnp.concatenate(
        [valid.astype(jnp.int32), jnp.zeros((pad_n,), jnp.int32)])
    # per-row INT8 quantization is row-local, so quantizing the T token
    # rows before the pack equals quantizing each assignment's copy
    payload, tok_scales = quantize_rows(x) if quantize else (x, None)
    buckets, rank, keep = route_pack_kernel(
        payload, dest_p, valid_p, k=k, n_dest=n_dest, capacity=capacity,
        bn=bn, interpret=interpret)
    rank, keep = rank[:N], keep[:N] > 0
    # the [n_dest, C] side planes are tiny: scatter them by the kernel's
    # rank/keep exactly as the reference does
    tok_of = jnp.arange(N) // k
    scales = (None if tok_scales is None else
              scatter_rows(tok_scales[tok_of], dest, rank, keep, n_dest,
                           capacity))
    eids = (None if eid is None else
            scatter_rows(eid.astype(jnp.int32), dest, rank, keep, n_dest,
                         capacity, fill=-1))
    return RoutePack(buckets, scales, eids, rank, keep)


def placement_route(dest: jax.Array, positions: jax.Array,
                    replica_slots: jax.Array,
                    n_replicas: jax.Array) -> jax.Array:
    """EPLB physical-slot indirection (§4.5 step 4).

    Maps logical expert ids to physical replica slots by *exact*
    round-robin of token position — the communication-free balancing
    rule the device-resident :class:`~repro.serving.eplb.PlacementTable`
    encodes::

        slot = replica_slots[dest, positions % n_replicas[dest]]

    ``dest`` [N] int32 logical ids; ``positions`` [N] int32 token
    positions (any monotone per-token counter works — the flattened
    token index in the decode batch here); ``replica_slots`` [E, R]
    int32 cyclically padded; ``n_replicas`` [E] int32 ≥ 1. With
    ``n_replicas == 1`` everywhere this is the identity bit-for-bit.
    """
    dest = dest.astype(jnp.int32)
    r = positions.astype(jnp.int32) % n_replicas[dest]
    return replica_slots[dest, r]


def placement_route_local(dest: jax.Array, positions: jax.Array,
                          replica_slots: jax.Array, n_replicas: jax.Array,
                          rank, n_local: int):
    """Sharded-EP view of :func:`placement_route`.

    Physical slots are block-sharded over the EP ranks — slot ``s``
    lives on rank ``s // n_local`` — so a hot expert's replicas land on
    different ranks and split its load across the pod. Returns
    ``(local_slot [N], mine [N] bool)``: the slot index within
    ``rank``'s shard and the slot-ownership mask that replaces plain
    sharded routing's logical ``flat_idx // E_local`` test
    (``models/ffn.py`` decode gather path). ``rank`` may be a traced
    scalar (``lax.axis_index`` inside ``shard_map``)."""
    phys = placement_route(dest, positions, replica_slots, n_replicas)
    mine = (phys // n_local) == rank
    return phys % n_local, mine


def fused_route_pack(x, dest, valid=None, eid=None, *, k: int = 1,
                     n_dest: int, capacity: int, quantize: bool = False,
                     use_pallas=None, interpret=None) -> RoutePack:
    """Capacity rank + INT8 quantize + bucket scatter in one jitted op
    (the rank and the row scatter are the Pallas kernel).

    x [T, d] payload rows (assignment ``r`` carries row ``r // k``);
    dest [N = T*k] int32 destinations in [0, n_dest]: ``n_dest`` marks
    an assignment that takes no slot (rank 0, never kept), while rows
    masked out by ``valid`` with a real destination still consume a rank
    slot of it, exactly like the reference chain; eid [N]
    optional int32 side payload bucketed with fill -1. Under EPLB
    placement, ``dest`` carries PHYSICAL slot ids (callers remap via
    :func:`placement_route`) and ``n_dest`` is the physical slot count.
    """
    if use_pallas is None:
        use_pallas = not on_cpu()
    return _dispatch(x, dest, valid, eid, k=k, n_dest=n_dest,
                     capacity=capacity, quantize=quantize,
                     use_pallas=bool(use_pallas),
                     interpret=resolve_interpret(interpret))

"""jit'd wrapper for the grouped expert FFN kernel (pads capacity/ff).

``use_pallas=None`` (default) picks the execution automatically: the
compiled Pallas kernel off-CPU, the jnp oracle on CPU (where the
interpreter would only add overhead inside jitted serving steps) — the
same convention as ``kernels/route_pack``. Tests pin ``use_pallas=True``
to validate the kernel in interpret mode against the oracle.

``phys_owner`` switches to the EPLB owner-indexed grouped matmul
(§4.5): buckets are per physical replica slot and slot ``s`` computes
against expert ``phys_owner[s]``'s weights, streamed block-by-block via
scalar-prefetch index maps instead of an owner-gathered
``[n_phys, d, f]`` weight materialization. The owner-indexed call is
bit-identical to ``expert_ffn(buckets, we_gate[phys_owner], ...)`` —
same block walk, same arithmetic (guarded in ``test_kernels.py``).

The Pallas paths carry no custom VJP — callers that differentiate
(train) must pass ``use_pallas=False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.gmm.kernel import gmm as _gmm
from repro.kernels.gmm.kernel import placement_gmm as _placement_gmm
from repro.kernels.gmm.ref import gmm_ref, placement_gmm_ref
from repro.kernels.runtime import on_cpu, resolve_interpret


# the TPU compiler's default scoped-VMEM limit (16 MiB on v5e) less room
# for the kernel's own temporaries (the [bc, bf] gate/up/hidden tiles)
_VMEM_BUDGET = 14 * 2**20


def _vmem_bytes(bc: int, bf: int, d: int, x_bytes: int,
                w_bytes: int) -> int:
    """VMEM one grid step holds: double-buffered token block, gate/up/
    down weight blocks and f32 output block, plus the f32 accumulator."""
    return (2 * bc * d * x_bytes + 2 * 3 * d * bf * w_bytes
            + 2 * bc * d * 4 + bc * d * 4)


def _blocks(C: int, d: int, f: int, x_bytes: int, w_bytes: int):
    """Largest (bc, bf) within :data:`_VMEM_BUDGET`: ``bc`` a multiple of
    8 up to 128 (capacity is padded up to a multiple of it), ``bf`` a
    lane-aligned divisor of ``f`` up to 512 (the whole ``f`` when it is
    not a multiple of 128). The ff blocks shrink first: they cost
    weight-block VMEM without changing how often weights stream."""
    bfs = [b for b in range(min(512, f) // 128 * 128, 0, -128)
           if f % b == 0] or [f]
    bc = min(128, -(-C // 8) * 8)
    while True:
        for bf in bfs:
            if _vmem_bytes(bc, bf, d, x_bytes, w_bytes) <= _VMEM_BUDGET:
                return bc, bf
        if bc == 8:
            raise ValueError(
                f"gmm: no block of d={d}, f={f} fits the VMEM budget")
        bc = max(8, bc // 2 // 8 * 8)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _dispatch(buckets, we_gate, we_up, we_down, phys_owner, *,
              use_pallas, interpret):
    if not use_pallas:
        if phys_owner is None:
            return gmm_ref(buckets, we_gate, we_up, we_down)
        return placement_gmm_ref(buckets, we_gate, we_up, we_down,
                                 phys_owner)
    E, C, d = buckets.shape
    f = we_gate.shape[-1]
    bc, bf = _blocks(C, d, f, buckets.dtype.itemsize,
                     we_gate.dtype.itemsize)
    padc = (-C) % bc
    if padc:
        buckets = jnp.pad(buckets, ((0, 0), (0, padc), (0, 0)))
    if phys_owner is None:
        out = _gmm(buckets, we_gate, we_up, we_down, bc=bc, bf=bf,
                   interpret=interpret)
    else:
        out = _placement_gmm(buckets, we_gate, we_up, we_down,
                             phys_owner, bc=bc, bf=bf,
                             interpret=interpret)
    return out[:, :C]


def expert_ffn(buckets, we_gate, we_up, we_down, *, phys_owner=None,
               use_pallas=None, interpret=None):
    """buckets [G, C, d] → [G, C, d] f32. With ``phys_owner=None``,
    G indexes the weight arrays directly; with ``phys_owner`` [G] int32,
    G is the physical-slot axis and slot ``s`` runs against
    ``we_*[phys_owner[s]]`` (gather-free owner-indexed GMM)."""
    if use_pallas is None:
        use_pallas = not on_cpu()
    return _dispatch(buckets, we_gate, we_up, we_down, phys_owner,
                     use_pallas=bool(use_pallas),
                     interpret=resolve_interpret(interpret))

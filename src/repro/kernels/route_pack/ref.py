"""Pure-jnp oracle for the fused route-pack kernel.

Deliberately a self-contained copy of the reference routing chain
(``capacity_rank`` + ``scatter_to_buckets`` + ``quantize_tokens`` from
``repro.xccl.routing``) so the kernel package has no dependency cycle
with the modules that call it. Bit-identity between this oracle, the
Pallas kernel, and the live ``xccl.routing`` helpers is enforced by
``tests/test_kernels.py`` and the hypothesis suite.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class RoutePack(NamedTuple):
    buckets: jax.Array               # [n_dest, C, d] int8 (quant) | payload
    scales: Optional[jax.Array]      # [n_dest, C] f32, quantize only
    eids: Optional[jax.Array]        # [n_dest, C] int32 (fill -1)
    rank: jax.Array                  # [N] int32 FIFO rank within dest
    keep: jax.Array                  # [N] bool  (rank < capacity & valid)


def _capacity_rank(dest, n_dest, capacity):
    """FIFO rank within each destination; ``dest == n_dest`` takes no
    slot (rank 0, never kept)."""
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1
    real = dest < n_dest
    my_rank = jnp.take_along_axis(ranks, jnp.where(real, dest, 0)[:, None],
                                  axis=1)[:, 0]
    my_rank = jnp.where(real, my_rank, 0)
    return my_rank, real & (my_rank < capacity)


def scatter_rows(values, dest, rank, keep, n_dest, capacity, fill=0):
    safe_rank = jnp.where(keep, rank, capacity)
    buf = jnp.full((n_dest, capacity + 1) + values.shape[1:], fill,
                   values.dtype)
    buf = buf.at[dest, safe_rank].set(values, mode="drop")
    return buf[:, :capacity]


def quantize_rows(x):
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def route_pack_ref(x, dest, valid=None, eid=None, *, k: int = 1,
                   n_dest: int, capacity: int,
                   quantize: bool = False) -> RoutePack:
    """x [T, d]; dest [N=T*k] int32 (already clamped to [0, n_dest));
    valid [N] bool (None ⇒ all valid); eid [N] int32 payload or None."""
    N = dest.shape[0]
    if valid is None:
        valid = jnp.ones((N,), bool)
    tok_of = jnp.arange(N) // k
    rank, in_cap = _capacity_rank(dest, n_dest, capacity)
    keep = in_cap & valid
    payload = x[tok_of]
    scales = None
    if quantize:
        qv, sc = quantize_rows(payload)
        buckets = scatter_rows(qv, dest, rank, keep, n_dest, capacity)
        scales = scatter_rows(sc, dest, rank, keep, n_dest, capacity)
    else:
        buckets = scatter_rows(payload, dest, rank, keep, n_dest,
                               capacity)
    eids = None
    if eid is not None:
        eids = scatter_rows(eid.astype(jnp.int32), dest, rank, keep,
                            n_dest, capacity, fill=-1)
    return RoutePack(buckets, scales, eids, rank.astype(jnp.int32), keep)

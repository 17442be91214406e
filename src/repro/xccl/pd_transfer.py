"""Device-layer KV/state transfer for disaggregated Prefill-Decode (§5.1).

On CloudMatrix the bytes move through XCCL send/recv over UB (or RoCE for
910B prefill). On a JAX deployment the analogue is ``jax.device_put`` of a
sharded pytree onto the decode mesh's shardings (XLA emits the
point-to-point transfers). The protocol concerns — deferred triggering,
handshakes, ordering, backpressure, isolated failure domains — live in
serving/distflow.py, which drives this module.

Because prefill and decode use DIFFERENT shardings (TP=4-style prefill vs
EP+DP decode; cache sequence-sharded on decode), the transfer includes a
reshard. ``plan_transfer`` computes per-leaf byte counts so DistFlow can
model/queue the transfer; ``execute_transfer`` performs it.

Chunk streaming (chunked prefill)
---------------------------------

With chunk-granular prefill, KV no longer ships as one post-hoc bulk
copy: each finished chunk's layers stream to the decode side WHILE the
next chunk computes. :func:`slice_kv_chunk` cuts one chunk's token range
out of a (partial) prefill cache, :func:`assemble_chunks` re-concatenates
received chunks on the decode side, and :func:`chunk_stream_time` is the
shared latency model of the compute/transfer pipeline — the exposed
transfer cost of a streamed prefill is essentially the LAST chunk's
transfer, everything earlier hides under later chunks' compute (the
overlap P/D-Serve and CloudMatrix-Infer rely on for TTFT tails).

The radix prefix cache stores a prompt's KV block by block:
:func:`slice_kv_blocks` cuts every whole block of a prefill cache in
one program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.xccl.topology import best_transfer_time

PyTree = Any


@dataclasses.dataclass
class TransferPlan:
    n_leaves: int
    total_bytes: int
    modeled_time_s: float
    fabric: str


def pytree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def plan_transfer(kv: PyTree, fabric: str = "ub") -> TransferPlan:
    """Metadata-only registration (paper §5.1 step 3: the PD-transfer task
    holds only metadata; data moves when the decode side triggers it)."""
    total = pytree_bytes(kv)
    return TransferPlan(
        n_leaves=len(jax.tree.leaves(kv)),
        total_bytes=total,
        modeled_time_s=best_transfer_time(total, fabric),
        fabric=fabric,
    )


def execute_transfer(kv: PyTree, dst_shardings: Optional[PyTree] = None)\
        -> PyTree:
    """Move/reshard the KV pytree onto the decode placement.

    dst_shardings: pytree of NamedSharding on the decode mesh (None →
    same-device handoff, used in single-host serving and tests).
    """
    if dst_shardings is None:
        return kv
    return jax.device_put(kv, dst_shardings)


# ---------------------------------------------------------------------------
# Chunk streaming
# ---------------------------------------------------------------------------
def _seq_axis(path) -> int:
    """Sequence axis of a KV-cache leaf: stacked superblock leaves
    ``[n_sb, B, L, ...]`` carry it at 2, prefix/tail leaves
    ``[B, L, ...]`` at 1 (the same path-key convention
    ``JAXBackend.write_slot`` uses for the batch axis)."""
    keys = [getattr(p, "key", None) for p in path]
    return 2 if "blocks" in keys else 1


def slice_kv_chunk(kv: PyTree, start: int, end: int) -> PyTree:
    """Cut token positions ``[start, end)`` out of a prefill cache —
    the per-chunk payload a streamed PD transfer ships. Only valid for
    sequence-addressed caches (ATTN / MLA), i.e. backends advertising
    ``supports_chunked_prefill``."""
    def one(path, leaf):
        ax = _seq_axis(path)
        idx = [slice(None)] * leaf.ndim
        idx[ax] = slice(start, end)
        return leaf[tuple(idx)]
    return jax.tree_util.tree_map_with_path(one, kv)


@functools.partial(jax.jit, static_argnames="block_size")
def slice_kv_blocks(kv: PyTree, block_size: int) -> List[PyTree]:
    """Cut every whole ``block_size``-token block of a prefill cache in
    one program: entry ``b`` equals ``slice_kv_chunk(kv, b * block_size,
    (b + 1) * block_size)`` in pytree, shape, dtype and bits. Each block
    is an output buffer of its own, never an alias of ``kv`` (nothing is
    donated), so it outlives a cache that a later program donates. One
    compile per cache shape; module-level, so every backend in the
    process shares the executables."""
    paths = jax.tree_util.tree_flatten_with_path(kv)[0]
    n = min(leaf.shape[_seq_axis(p)] for p, leaf in paths) // block_size

    def block(b):
        return jax.tree_util.tree_map_with_path(
            lambda p, leaf: jax.lax.slice_in_dim(
                leaf, b * block_size, (b + 1) * block_size,
                axis=_seq_axis(p)), kv)
    return [block(b) for b in range(n)]


def assemble_chunks(chunks: Sequence[PyTree]) -> PyTree:
    """Decode-side reassembly: concatenate received chunk payloads back
    into one contiguous cache along the sequence axis (inverse of
    :func:`slice_kv_chunk` over consecutive ranges)."""
    if not chunks:
        raise ValueError("no chunks to assemble")
    if len(chunks) == 1:
        return chunks[0]
    import jax.numpy as jnp

    def cat(path, *leaves):
        return jnp.concatenate(leaves, axis=_seq_axis(path))
    return jax.tree_util.tree_map_with_path(cat, chunks[0], *chunks[1:])


def ub_read(kv: PyTree) -> PyTree:
    """One-sided UB global-shared-memory read of a remote DP's stored KV.

    CloudMatrix-Infer's pod-pooled prefix cache lets any NPU read any
    cached block over the UB plane without involving the owner's compute
    stream; the owner only has to keep the blocks pinned (the
    `PodKVDirectory.acquire` remote pin) for the duration of the read.
    On a JAX deployment the analogue is materializing fresh arrays from
    the owner's stored payloads — bit-identical to the source, so a
    remote-hit-seeded prefill stays exactly equal to a local-hit or cold
    one.  Non-array leaves (the cost-model backend's dict payloads) pass
    through unchanged."""
    import jax.numpy as jnp

    def one(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jnp.asarray(leaf)
        return leaf
    return jax.tree.map(one, kv)


def ub_read_time(total_bytes: int, fabric: str = "ub") -> float:
    """Modeled wire time of a pooled-KV read (same link model the
    chunk-streamed PD transfer prices with)."""
    return best_transfer_time(int(total_bytes), fabric)


def chunk_stream_time(chunk_bytes: Sequence[int],
                      chunk_compute_s: Sequence[float],
                      fabric: str = "ub") -> Tuple[float, float]:
    """Latency model of layer/chunk-overlapped KV streaming.

    Chunk ``i``'s transfer starts when its compute finishes and the link
    is free; chunk ``i+1``'s compute runs concurrently. Returns
    ``(total_time, exposed_transfer)`` where ``exposed_transfer`` is the
    transfer time NOT hidden under compute — for well-sized chunks this
    is just the final chunk's wire time, vs the whole cache's for a
    post-hoc bulk copy."""
    if len(chunk_bytes) != len(chunk_compute_s):
        raise ValueError("chunk_bytes and chunk_compute_s must align")
    t = 0.0
    link_free = 0.0
    for nbytes, compute in zip(chunk_bytes, chunk_compute_s):
        t += compute                      # compute end of this chunk
        start = max(t, link_free)
        link_free = start + best_transfer_time(int(nbytes), fabric)
    total = max(link_free, t)
    return total, total - t

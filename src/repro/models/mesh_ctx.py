"""Mesh context threaded through model code.

A single :class:`MeshCtx` describes how model code should map onto the
device mesh. Smoke tests use a 1×1 mesh so every code path (shard_map,
collectives) is identical between CPU tests and the 512-device dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)   # ("pod","data") multi-pod
    tp_axis: str = "model"                    # tensor-parallel axis
    ep_axis: str = "model"                    # expert-parallel axis
    seq_axis: str = "model"                   # KV-cache / sequence shard axis
    # MoE execution strategy: "alltoall" (train/prefill; paper dispatch/
    # combine) or "gather" (decode; paper pull-based dispatch over shared
    # memory → gather-compute-reduce).
    moe_impl: str = "alltoall"
    # shard the decode KV cache along sequence over seq_axis (flash-decoding
    # style distributed attention). Beyond-paper optimization; can be
    # disabled to get the paper-faithful TP=1 replicated-KV decode.
    shard_kv_seq: bool = True
    # remat policy for scanned superblocks: "none" | "full"
    remat: str = "full"
    use_pallas: bool = False    # route hot ops through Pallas kernels
    # §4.4 decode ping-pong: split each decode MoE batch into this many
    # micro-batches so dispatch/combine of one overlaps expert compute
    # of the other (1 = off; 2 = the paper's setting)
    decode_microbatches: int = 1

    # ------------------------------------------------------------------
    @property
    def bspec(self):
        """Batch PartitionSpec entry: tuple of axes, or None (batch too
        small to shard, e.g. long_500k's global_batch=1)."""
        return tuple(self.batch_axes) if self.batch_axes else None

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return self.mesh.axis_names

    def axis_size(self, name) -> int:
        if name is None:
            return 1
        if isinstance(name, (tuple, list)):
            out = 1
            for n in name:
                out *= self.axis_size(n)
            return out
        return self.mesh.shape[name]

    @property
    def dp_size(self) -> int:
        return self.axis_size(self.batch_axes)

    @property
    def tp_size(self) -> int:
        return self.axis_size(self.tp_axis)

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_auto_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the model code places
    arrays with ``NamedSharding``/``shard_map`` and lets the partitioner
    propagate the rest, so shardings stay out of the array types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_smoke_ctx(**kw) -> MeshCtx:
    """1×1 mesh on the first device — same code paths, no sharding."""
    mesh = make_auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    kw.setdefault("remat", "none")
    return MeshCtx(mesh=mesh, batch_axes=("data",), **kw)

"""Pallas TPU kernel: route-pack (§3.2 dispatch packing).

One streaming pass over the routed assignments replaces the
O(N·E)-memory ``one_hot``/``cumsum``/``scatter`` chain that
``xccl/routing.py`` and ``models/ffn.py`` used to build capacity
buckets. The capacity rank is a scalar walk: assignment blocks land in
SMEM, a per-destination running count lives in SMEM scratch across
grid steps (no [N, E] tensor anywhere), and each kept assignment
starts one row DMA straight from the payload in HBM to its
``(dest, rank)`` bucket row in HBM. No payload byte passes through
VMEM, so the bucket plane is bounded by HBM, not by VMEM: a 2,048-token
deepseek-moe-16b prefill chunk packs 64 × 288 × 2048 bf16 ≈ 75 MB.

Layout contract (``ops.py`` pads and handles the side payloads):

* ``x``       [T, d]   payload rows in HBM, viewed as [T, d/128, 128];
  assignment ``r`` reads row ``r // k`` (the top-k repeat is a DMA
  source index, never a materialized [N, d] gather).
* ``dest``    [Np]     destination bucket per assignment; rows carrying
  ``dest >= n_dest`` are padding and consume no rank slots.
* ``valid``   [Np]     0 masks an assignment out of ``keep`` (it still
  consumes a rank slot of its destination, matching the reference
  ``capacity_rank(where(valid, dest, 0))`` semantics).
* ``zeros``   [n_dest, C, d/128, 128]  aliased to the bucket output:
  rows no assignment lands in keep their zero fill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(dest_ref, valid_ref, x_hbm, zeros_hbm, buckets_hbm, rank_ref,
            keep_ref, counts_ref, sem, *, k: int, n_dest: int,
            capacity: int):
    del zeros_hbm                         # aliased to buckets_hbm
    i = pl.program_id(0)
    bn = dest_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        def zero(j, c):
            counts_ref[j] = 0
            return c
        jax.lax.fori_loop(0, n_dest, zero, 0)

    def row_copy(src_row, dst, rk):
        return pltpu.make_async_copy(x_hbm.at[src_row],
                                     buckets_hbm.at[dst, rk], sem)

    def assign(r, n_started):
        dst = dest_ref[r]
        real = dst < n_dest
        safe = jnp.where(real, dst, 0)
        rk = counts_ref[safe]
        counts_ref[safe] = rk + real.astype(jnp.int32)
        keep = real & (rk < capacity) & (valid_ref[r] > 0)
        rank_ref[r] = jnp.where(real, rk, 0)
        keep_ref[r] = keep.astype(jnp.int32)

        @pl.when(keep)
        def _():
            row_copy((i * bn + r) // k, safe, rk).start()
        return n_started + keep.astype(jnp.int32)

    n_started = jax.lax.fori_loop(0, bn, assign, 0)

    def drain(j, c):
        row_copy(0, 0, 0).wait()          # every copy moves one row
        return c
    jax.lax.fori_loop(0, n_started, drain, 0)


@functools.partial(jax.jit, static_argnames=("k", "n_dest", "capacity",
                                             "bn", "interpret"))
def route_pack_kernel(x, dest, valid, *, k: int, n_dest: int,
                      capacity: int, bn: int, interpret: bool = True):
    """Pre-padded entry (``ops.py`` handles padding/unpadding).

    x [T, d]; dest/valid [Np] int32 with Np % bn == 0 and every real
    assignment ``r < T * k``. Returns (buckets [n_dest, C, d] of
    x.dtype, rank [Np] int32, keep [Np] int32)."""
    T, d = x.shape
    Np = dest.shape[0]
    assert Np % bn == 0
    # each payload row travels as a whole [d/128, 128] tile slab under a
    # major (untiled) index: HBM tiles span 8-32 rows, so a single row of
    # a plain [T, d] array is not addressable by DMA
    lanes = 128 if d % 128 == 0 else d
    row = (d // lanes, lanes)
    blk = pl.BlockSpec((bn,), lambda i: (i,), memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    zeros = jnp.zeros((n_dest, capacity) + row, x.dtype)
    buckets, rank, keep = pl.pallas_call(
        functools.partial(_kernel, k=k, n_dest=n_dest, capacity=capacity),
        grid=(Np // bn,),
        in_specs=[blk, blk, hbm, hbm],
        out_specs=(hbm, blk, blk),
        out_shape=(
            jax.ShapeDtypeStruct((n_dest, capacity) + row, x.dtype),
            jax.ShapeDtypeStruct((Np,), jnp.int32),
            jax.ShapeDtypeStruct((Np,), jnp.int32),
        ),
        scratch_shapes=[pltpu.SMEM((n_dest,), jnp.int32),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={3: 0},
        # the running counts carry across blocks: strictly sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(dest, valid, x.reshape((T,) + row), zeros)
    return buckets.reshape(n_dest, capacity, d), rank, keep

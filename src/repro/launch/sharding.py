"""Logical-axis sharding rules with divisibility fallback.

Parameters are plain nested dicts; rules key on (leaf name, ndim) and
assign each dim a *logical axis*. A resolver then maps logical axes to
mesh axes, replicating any dim whose size does not divide the mesh-axis
product or whose mesh axes are already taken by another dim of the same
parameter. This is what lets e.g. recurrentgemma's 10-head attention
(indivisible by a 16-way model axis) lower cleanly: heads fall back to
replication while d_ff=7680 still shards 16-way.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.mesh_ctx import MeshCtx

PyTree = Any

# logical axis → ordered candidate mesh-axis tuples (first fit wins)
LOGICAL_RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "vocab": (("model",), ("data",)),
    "embed": (("data",),),          # FSDP-style shard of d_model
    "heads": (("model",),),
    "kv_heads": (("model",),),
    "mlp": (("model",),),
    "expert": (("model",),),
    "expert_mlp": (("data",),),
    "lru": (("model",),),
    "ssm_inner": (("model",),),
    None: (),
}

# Decode profile (§Perf hillclimb): FSDP 'embed' sharding is great for
# train (per-layer all-gathers amortize over thousands of tokens) but at
# decode it re-gathers EVERY weight EVERY token step — the dominant
# collective term in the baseline dry-runs (e.g. command-r-35b decode_32k:
# 120 ms/step of all-gather). The decode profile replicates weights over
# 'data' (memory is ample at per-device batch ≤ 8) and instead shards
# experts over BOTH axes (the paper's actual EP-per-die layout).
DECODE_RULES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    **LOGICAL_RULES,
    "embed": (),                       # replicate weights over data
    "expert": (("data", "model"), ("model",)),   # EP across the pod
    "expert_mlp": (),
}

# (leaf name, ndim) → logical axes per dim. None = replicated dim.
PARAM_RULES: Dict[Tuple[str, int], Tuple[Optional[str], ...]] = {
    ("embed", 2): ("vocab", "embed"),
    ("lm_head", 2): ("embed", "vocab"),
    # attention
    ("wq", 3): ("embed", "heads", None),
    ("wk", 3): ("embed", "kv_heads", None),
    ("wv", 3): ("embed", "kv_heads", None),
    ("wo", 3): ("heads", None, "embed"),
    # MLA
    ("wq_a", 2): ("embed", None),
    ("wq_b", 3): (None, "heads", None),
    ("wkv_a", 2): ("embed", None),
    ("wk_b", 3): (None, "heads", None),
    ("wv_b", 3): (None, "heads", None),
    # mlp
    ("wi_gate", 2): ("embed", "mlp"),
    ("wi_up", 2): ("embed", "mlp"),
    ("wo", 2): ("mlp", "embed"),
    # moe
    ("router", 2): (None, None),
    ("we_gate", 3): ("expert", None, "expert_mlp"),
    ("we_up", 3): ("expert", None, "expert_mlp"),
    ("we_down", 3): ("expert", "expert_mlp", None),
    # rglru
    ("w_in", 2): ("embed", "lru"),
    ("w_gate_branch", 2): ("embed", "lru"),
    ("w_out", 2): ("lru", "embed"),
    # ssm
    ("in_proj", 2): ("embed", "ssm_inner"),
    ("out_proj", 2): ("ssm_inner", "embed"),
    # mtp
    ("proj", 2): ("embed", None),
}


def _resolve(shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
             mesh: Mesh, rules=None) -> P:
    """Greedy per-dim assignment with divisibility + axis-uniqueness."""
    rules = rules or LOGICAL_RULES
    used = set()
    entries = []
    for size, lname in zip(shape, logical):
        assigned = None
        for cand in rules.get(lname, ()):
            axes = tuple(a for a in cand if a in mesh.shape)
            if not axes or any(a in used for a in axes):
                continue
            prod = int(np.prod([mesh.shape[a] for a in axes]))
            if prod > 1 and size % prod == 0:
                assigned = axes if len(axes) > 1 else axes[0]
                used.update(axes)
                break
        entries.append(assigned)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_pspecs(params_shape: PyTree, mesh: Mesh, rules=None) -> PyTree:
    """Build a PartitionSpec pytree matching an eval_shape'd params tree.

    Scan-stacked subtrees (under 'blocks' or MoE expert dims inside them)
    are detected by path: any leaf whose path includes 'blocks' has a
    leading layer-stack dim that is never sharded.
    """
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    treedef = jax.tree_util.tree_structure(params_shape)
    specs = []
    for path, leaf in flat:
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        name = keys[-1] if isinstance(keys[-1], str) else "?"
        stacked = "blocks" in keys
        shape = leaf.shape
        core_shape = shape[1:] if stacked else shape
        rule = PARAM_RULES.get((name, len(core_shape)))
        if rule is None:
            spec = P()
        else:
            spec = _resolve(core_shape, rule, mesh, rules)
        if stacked and len(spec) > 0:
            spec = P(*((None,) + tuple(spec)))
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params_shape: PyTree, mesh: Mesh,
                    rules=None) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params_shape, mesh, rules))


def init_params(model, key) -> PyTree:
    """Initialise ``model``'s params from ``key`` in ONE jitted call,
    each leaf created in place with its :func:`param_shardings` layout on
    the model's mesh (experts split over the EP axis, heads and ff over
    the TP axis), so no leaf is ever materialised whole on one device."""
    shapes = jax.eval_shape(model.init, key)
    return jax.jit(model.init, out_shardings=param_shardings(
        shapes, model.ctx.mesh))(key)


# ---------------------------------------------------------------------------
# Cache shardings
# ---------------------------------------------------------------------------
def cache_pspecs(cache_spec: PyTree, ctx: MeshCtx) -> PyTree:
    """KV/state caches: batch over batch_axes; the sequence dim (dim 1 of
    4-D k/v and 3-D ckv/krope leaves) over seq_axis when divisible."""
    b = ctx.bspec
    seq = ctx.seq_axis if ctx.shard_kv_seq else None
    seq_size = ctx.axis_size(ctx.seq_axis)
    bsize = ctx.dp_size

    def one(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        stacked = "blocks" in keys
        name = keys[-1]
        shape = leaf.shape[1:] if stacked else leaf.shape
        bdim = b if shape[0] % max(bsize, 1) == 0 and bsize > 1 else None
        if name in ("k", "v", "ckv", "krope"):
            sdim = seq if seq and shape[1] % seq_size == 0 else None
            spec = (bdim, sdim) + (None,) * (len(shape) - 2)
        else:
            spec = (bdim,) + (None,) * (len(shape) - 1)
        if stacked:
            spec = (None,) + spec
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache_spec)


def cache_shardings(cache_spec: PyTree, ctx: MeshCtx) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                        cache_pspecs(cache_spec, ctx))


def batch_pspec(ctx: MeshCtx, global_batch: int) -> P:
    if ctx.dp_size > 1 and global_batch % ctx.dp_size == 0:
        return P(ctx.bspec)
    return P(None)

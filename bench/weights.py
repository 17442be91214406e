"""Weights from the seed, made by the benchmark and handed to the
program (and, made again, to the reference), so that the reference
takes nothing the program made.

The tree has the layout the program's ``Model.init`` declares (read
with ``jax.eval_shape``: shapes only). Every leaf is drawn in one jitted
call on the device, in the type it is served in: matrices from a normal
of variance ``1 / fan_in``, embedding rows of unit variance, RMSNorm
scales (stored as their difference from 1) from a normal of standard
deviation 0.1 so that the norms' weights matter.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

#: input axes of leaves whose inputs are not all axes but the last
FAN_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,),
               "we_gate": (1,), "we_up": (1,), "we_down": (1,)}


def key(seed: int):
    """A PRNG key for any whole number up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _leaf(k, name: str, shape, dtype, stacked: bool):
    core = shape[1:] if stacked else shape
    if name.endswith("norm") or name.startswith("norm_"):
        return (0.1 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
    if name == "embed":
        return jax.random.normal(k, shape, dtype)
    axes = FAN_IN_AXES.get(name, tuple(range(len(core) - 1)))
    fan_in = int(np.prod([core[a] for a in axes]))
    scale = 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(k, shape, dtype) * scale).astype(dtype)


def make(model, seed: int, shardings=None):
    """The params tree of ``model`` drawn from ``seed``."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def gen(k):
        leaves = []
        for i, (path, s) in enumerate(flat):
            keys = [getattr(p, "key", None) for p in path]
            name = next(x for x in reversed(keys) if isinstance(x, str))
            leaves.append(_leaf(jax.random.fold_in(k, i), name, s.shape,
                                s.dtype, "blocks" in keys))
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(gen, out_shardings=shardings)(key(seed))

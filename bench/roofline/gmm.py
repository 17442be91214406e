"""The grouped expert FFN (``kernels/gmm``): gate, up, SiLU and down
projections of the routed assignments.

Required work of one call over ``n_tokens`` live tokens: ``2 * 3 * d *
f`` FLOPs per routed assignment that was kept (every one, routing being
dropless), not per padded capacity row; bytes are the weights of the
experts that at least one assignment reached, read once, plus each
assignment's row in (bf16) and out (f32).
"""
from __future__ import annotations

from bench.roofline import experts_reached


def work(conf: dict, n_tokens: float):
    """(FLOPs, bytes) of one expert-FFN call over ``n_tokens`` tokens."""
    d = conf["hidden_size"]
    f = conf["moe_intermediate_size"]
    E = conf["n_routed_experts"]
    k = conf["num_experts_per_tok"]
    n_assign = n_tokens * k
    flops = 2.0 * 3 * d * f * n_assign
    weights = experts_reached(n_tokens, E, k) * 3 * d * f * 2
    rows = n_assign * d * (2 + 4)
    return flops, weights + rows

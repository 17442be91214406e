"""One decode step of the whole model for one DP group (the program's
decode+sample call).

Required work for ``n_live`` live slots whose contexts hold ``ctx_sum``
positions in all: every weight the step uses read once (routed experts
only as far as some token reaches them; the embedding only for the rows
looked up), the keys and values of each slot's live context (not the
whole ``max_len`` buffer), and the new tokens' keys and values written.
FLOPs are ``2`` per weight per token plus attention over the live
context.
"""
from __future__ import annotations

from bench.roofline import experts_reached


def _layers(conf: dict):
    """(dense layers, MoE layers)."""
    n = conf["num_hidden_layers"]
    if "n_routed_experts" not in conf:
        return n, 0
    dense = conf.get("first_k_dense_replace", 0)
    return dense, n - dense


def shapes(conf: dict):
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    KV = conf.get("num_key_value_heads", H)
    hd = d // H
    return d, H, KV, hd


def attn_params(conf: dict) -> int:
    d, H, KV, hd = shapes(conf)
    return d * (H + 2 * KV) * hd + H * hd * d


def kv_bytes_per_token(conf: dict) -> int:
    """Keys and values of one position over all layers, bf16."""
    d, H, KV, hd = shapes(conf)
    return conf["num_hidden_layers"] * 2 * KV * hd * 2


def flops_per_token(conf: dict, context: float) -> float:
    """Forward FLOPs of one token at ``context`` attended positions,
    counting the routed experts it uses (not all of them) and the
    output head."""
    d, H, KV, hd = shapes(conf)
    dense, moe = _layers(conf)
    L = dense + moe
    per = L * attn_params(conf) + dense * 3 * d * conf["intermediate_size"]
    if moe:
        f = conf["moe_intermediate_size"]
        per += moe * (d * conf["n_routed_experts"]
                      + (conf["num_experts_per_tok"]
                         + conf.get("n_shared_experts", 0)) * 3 * d * f)
    per += d * conf["vocab_size"]
    return 2.0 * per + 4.0 * L * H * hd * context


def work(conf: dict, n_live: int, ctx_sum: float):
    """(FLOPs, bytes) of one decode step."""
    if n_live <= 0:
        return 0.0, 0.0
    d, H, KV, hd = shapes(conf)
    dense, moe = _layers(conf)
    L = dense + moe
    w = L * attn_params(conf) + dense * 3 * d * conf["intermediate_size"]
    if moe:
        f = conf["moe_intermediate_size"]
        E, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
        w += moe * (d * E + conf.get("n_shared_experts", 0) * 3 * d * f
                    + experts_reached(n_live, E, k) * 3 * d * f)
    w += d * conf["vocab_size"] + n_live * d
    flops = (n_live * flops_per_token(conf, 0)
             + 4.0 * L * H * hd * (ctx_sum + n_live))
    kv = kv_bytes_per_token(conf) * (ctx_sum + n_live)
    return flops, 2.0 * w + kv

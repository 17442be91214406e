"""Plain float32 reference of the decoder the configuration files
describe, written from the published description and independent of
the program: RMSNorm, rotary positions (rotate-half), grouped-query
causal attention, a SwiGLU MLP or a mixture of experts (softmax router,
top-k, optionally renormalised weights, shared experts as one wider
SwiGLU), final RMSNorm and an untied output head. Every matmul runs in
float32 at ``HIGHEST`` precision over the whole sequence at once: no
cache, no batching, no kernels.

It reads weights laid out as the serving program stores them (the
benchmark makes them, see ``weights.py``): norm scales are kept as
their difference from 1; attention weights are ``wq [d, H, hd]``, ``wk``
/ ``wv [d, KV, hd]``, ``wo [H, hd, d]`` and query head ``h`` reads key
head ``h // (H / KV)``; layers of one kind are stacked on a leading
axis under ``blocks``.

``rounding="fp8"`` gives the control: the same computation with every
weight and every value the served program stores in bfloat16
(activations, the residual stream, norms, attention probabilities,
matmul outputs) rounded to fp8's mantissa (e4m3: 3 bits; the exponent
range is left unbounded), the precision below the bfloat16 the
configurations state. ``rounding="bf16"`` rounds the same values to
bfloat16's 7 bits: the reference computed as the program stores it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


MANTISSA_BITS = {"fp8": 3, "bf16": 7}


def _rounder(rounding: Optional[str]):
    """Round-to-nearest onto ``rounding``'s mantissa width (identity for
    None)."""
    if rounding is None:
        return lambda x: x
    scale = 2.0 ** (MANTISSA_BITS[rounding] + 1)

    def rnd(x):
        m, e = jnp.frexp(x)
        return jnp.ldexp(jnp.round(m * scale) / scale, e)
    return rnd


def _mm(spec: str, x, w, r):
    """``einsum(spec, x, w)`` in float32 at HIGHEST, inputs and output
    rounded by ``r``."""
    return r(jnp.einsum(spec, r(x.astype(F32)), r(w.astype(F32)),
                        precision=HI))


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(F32))


def _rope(x, theta: float):
    """x [S, heads, hd]; position of row i is i."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, conf, r):
    S = h.shape[0]
    q = _mm("sd,dhk->shk", h, p["wq"], r)
    k = _mm("sd,dhk->shk", h, p["wk"], r)
    v = _mm("sd,dhk->shk", h, p["wv"], r)
    theta = float(conf["rope_theta"])
    q, k = r(_rope(q, theta)), r(_rope(k, theta))
    H, KV, hd = q.shape[1], k.shape[1], q.shape[2]
    kh = jnp.repeat(k, H // KV, axis=1)        # query head h -> h // G
    vh = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhk,phk->hqp", q, kh, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqp,phk->qhk", r(jax.nn.softmax(s, -1)), vh,
                   precision=HI)
    return _mm("shk,hkd->sd", o, p["wo"], r)


def _swiglu(p, h, r):
    g = _mm("sd,df->sf", h, p["wi_gate"], r)
    u = _mm("sd,df->sf", h, p["wi_up"], r)
    return _mm("sf,fd->sd", r(jax.nn.silu(g) * u), p["wo"], r)


def _moe(p, h, conf, r):
    k = int(conf["num_experts_per_tok"])
    logits = jnp.einsum("sd,de->se", h, p["router"].astype(F32),
                        precision=HI)
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, k)
    if conf.get("norm_topk_prob", False):
        top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)        # [S, E]

    def expert(y, xs):
        wg, wu, wd, g = xs
        a = _mm("sd,df->sf", h, wg, r)
        b = _mm("sd,df->sf", h, wu, r)
        out = _mm("sf,fd->sd", r(jax.nn.silu(a) * b), wd, r)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["we_gate"], p["we_up"], p["we_down"], gate.T))
    y = r(y)
    if "shared" in p:
        y = y + _swiglu(p["shared"], h, r)
    return y


def _block(p, x, conf, r):
    eps = float(conf["rms_norm_eps"])
    x = r(x + _attention(p["mixer"], r(_rms(x, p["mixer_norm"], eps)),
                         conf, r))
    h = r(_rms(x, p["ffn_norm"], eps))
    if "router" in p["ffn"]:
        return r(x + _moe(p["ffn"], h, conf, r))
    return r(x + _swiglu(p["ffn"], h, r))


def _forward(params, tokens, rows, conf, r):
    x = r(params["embed"][tokens].astype(F32))
    for p in params.get("prefix", ()):
        x = _block(p, x, conf, r)
    if "blocks" in params:
        def body(x, sb):
            for key in sorted(sb, key=lambda k: int(k[3:])):
                x = _block(sb[key], x, conf, r)
            return x, None
        x, _ = jax.lax.scan(body, x, params["blocks"])
    for p in params.get("tail", ()):
        x = _block(p, x, conf, r)
    x = r(_rms(x, params["final_norm"], float(conf["rms_norm_eps"])))
    return jnp.einsum("sd,dv->sv", x[rows], r(params["lm_head"].astype(F32)),
                      precision=HI)


@functools.partial(jax.jit, static_argnames=("conf_items", "rounding"))
def _scores(params, tokens, rows, judged, *, conf_items, rounding):
    """Reference best logit and logit of ``judged`` at each row; with
    ``rounding``, ``judged`` is replaced by the control's first choice."""
    conf = dict(conf_items)
    ref = _forward(params, tokens, rows, conf, _rounder(None))
    if rounding is not None:
        judged = jnp.argmax(_forward(params, tokens, rows, conf,
                                     _rounder(rounding)), -1)
    got = jnp.take_along_axis(ref, judged[:, None].astype(jnp.int32),
                              -1)[:, 0]
    return jnp.max(ref, -1), got


_CONF_KEYS = ("rope_theta", "rms_norm_eps", "num_experts_per_tok",
              "norm_topk_prob")


def gaps(params, conf: dict, tokens, rows, judged,
         rounding: Optional[str] = None) -> np.ndarray:
    """Gap of the reference's best logit over its logit of token
    ``judged[i]`` at position ``rows[i]`` of the sequence ``tokens``
    (with ``rounding``, of the token the control puts first there).
    Tokens are padded to the engine's ``max_len`` and rows to a multiple
    of 512, so one compiled program serves every request of a cell;
    causal attention keeps padded tokens out of earlier positions."""
    if rounding not in (None, *MANTISSA_BITS):
        raise ValueError(f"unknown rounding {rounding!r}")
    n, m = len(tokens), len(rows)
    tok = np.zeros(max(conf["engine"]["max_len"], -(-n // 256) * 256),
                   np.int32)
    tok[:n] = tokens
    row = np.zeros(-(-m // 512) * 512, np.int32)
    row[:m] = rows
    jud = np.zeros(len(row), np.int32)
    jud[:m] = judged
    items = tuple((k, conf[k]) for k in _CONF_KEYS if k in conf)
    best, got = _scores(params, jnp.asarray(tok), jnp.asarray(row),
                        jnp.asarray(jud), conf_items=items,
                        rounding=rounding)
    return (np.asarray(best, np.float64) - np.asarray(got, np.float64))[:m]

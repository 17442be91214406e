"""Bring-up smoke of the FlowServe serving path on a TPU.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # EP-4 deepseek-moe-16b on (1, 4)

Drives ``FlowServeEngine`` -> ``DPGroup`` -> ``JAXBackend`` -> ``Model``
at published widths with random weights drawn from ``--seed``:

* Phase A: internlm2-1.8b, 8 greedy requests of mixed prompt lengths up
  to 1,000 tokens, 32 new tokens each. Checks that every request gets
  its tokens, that a repeated prompt decodes to the same tokens, and
  that a decode step's logits match prefill logits at that position.
* Phase B: deepseek-moe-16b cut to 4 layers (1 dense + 3 MoE), 4 greedy
  requests. Checks the same token properties, that the decode step's
  HLO holds the Pallas kernels (``tpu_custom_call``), that the Pallas
  expert FFN matches its jnp reference, and that the Pallas route-pack
  equals its reference exactly.
* ``--four-chips``: the whole 28-layer deepseek-moe-16b served with its
  experts split over 4 chips, then the 4-layer cut served on 4 chips
  and on 1 chip (float32, dropless capacity) with equal greedy tokens
  and prefill logits within a band. No other phase runs.

Per-phase figures (compile and run seconds, tokens, peak device bytes)
are bring-up figures, not benchmark results. Any failed check exits
non-zero. The last line of stdout is the JSON device record.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# prefill logits vs a decode step's logits at the same position, as a
# share of the largest |logit| (tests/test_decode_consistency.py band)
DECODE_BAND = 0.08
# Pallas expert FFN vs its f32 jnp reference: the kernel rounds the
# SiLU(g)*u hidden to bf16 before the down projection
GMM_BAND = 2e-2
# EP-4 vs one chip in float32: only the order of f32 sums differs
EP_BAND = 1e-3


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  ok: {what}", flush=True)


class CompileMeter:
    """Sums JAX's own compile events: XLA backend compile seconds (a
    persistent-cache hit counts only its read), jaxpr-to-MLIR lowering
    seconds, and persistent-cache hits. Python tracing is not split out
    (nested jits would count twice): it stays in a phase's ``run_s``."""

    def __init__(self):
        import jax

        self.backend_s = self.trace_s = 0.0
        self.cache_hits = 0

        def duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.backend_s += secs
            elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.trace_s += secs

        def count(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(count)

    def snapshot(self):
        return self.backend_s, self.trace_s, self.cache_hits


def mem(devices, key):
    """Per-device allocator figure (``None`` where the backend keeps no
    allocator statistics)."""
    return [(d.memory_stats() or {}).get(key) for d in devices]


def report(name, meter, before, wall_s, n_tokens, devices):
    b0, t0, h0 = before
    b1, t1, h1 = meter.snapshot()
    rec = {"phase": name, "compile_s": round(b1 - b0, 3),
           "lower_s": round(t1 - t0, 3), "cache_hits": h1 - h0,
           "wall_s": round(wall_s, 3),
           "run_s": round(wall_s - (b1 - b0) - (t1 - t0), 3),
           "tokens": n_tokens,
           "peak_bytes_in_use": mem(devices, "peak_bytes_in_use")}
    print("bring-up figures (not a benchmark): " + json.dumps(rec),
          flush=True)


def prompts(rng, lens, vocab):
    """Token prompts drawn from ``rng``; ids start above the byte
    tokenizer's specials."""
    return [rng.integers(2, vocab, n).tolist() for n in lens]


def serve(engine, toks, max_new):
    """Submit greedy requests through the engine and run them to the
    end; returns the requests in submission order."""
    from repro.serving.request import Request

    reqs = [Request(prompt_tokens=list(t), max_new_tokens=max_new,
                    ignore_eos=True) for t in toks]
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    return reqs


def check_served(reqs, max_new, vocab, dup):
    got = [len(r.output_tokens) for r in reqs]
    check(all(n == max_new for n in got),
          f"every request got its {max_new} tokens ({got})")
    flat = np.concatenate([np.asarray(r.output_tokens) for r in reqs])
    check(bool(np.all((flat >= 0) & (flat < vocab))),
          "every token id is inside the vocabulary")
    a, b = dup
    check(reqs[a].output_tokens == reqs[b].output_tokens,
          f"repeated prompt (requests {a} and {b}) decodes identically")


def decode_vs_prefill(engine, toks, n):
    """Logits of one decode step at position ``n`` against prefill
    logits of the same ``n + 1`` tokens, through the engine's backend
    programs. Returns max |diff| / max |prefill logit|."""
    be = engine.dps[0].backend
    cache1, _ = be.prefill_chunk(None, toks[:n], 0, n)
    _, ref = be.prefill_chunk(None, toks[:n + 1], 0, n + 1)
    B = engine.dps[0].max_batch
    cache = be.write_slot(be.init_cache(B, engine.max_len), cache1, 0)
    tokens = np.zeros((B, 1), np.int32)
    tokens[0, 0] = toks[n]
    positions = np.zeros((B,), np.int32)
    positions[0] = n
    got, _ = be.decode(cache, tokens, positions)
    return float(np.max(np.abs(got[0] - ref)) / np.max(np.abs(ref)))


def determinism(engine, toks):
    """Largest |logit| difference between two runs of one program on one
    input: a prefill repeated on DP group 0, the same prefill on DP
    groups 0 and 1, and one decode step after it on each group."""
    a, b = (d.backend for d in engine.dps[:2])
    n = len(toks)
    ca, la = a.prefill_chunk(None, toks, 0, n)
    _, la2 = a.prefill_chunk(None, toks, 0, n)
    cb, lb = b.prefill_chunk(None, toks, 0, n)
    B = engine.dps[0].max_batch
    tokens = np.zeros((B, 1), np.int32)
    tokens[0, 0] = int(np.argmax(la))
    positions = np.zeros((B,), np.int32)
    positions[0] = n

    def decode(be, cache1):
        cache = be.write_slot(be.init_cache(B, engine.max_len), cache1, 0)
        return be.decode(cache, tokens, positions)[0][0]

    diff = lambda x, y: float(np.max(np.abs(x - y)))  # noqa: E731
    return {"prefill_repeat": diff(la, la2),
            "prefill_across_dp": diff(la, lb),
            "decode_across_dp": diff(decode(a, ca), decode(b, cb))}


# ---------------------------------------------------------------------------
def phase_dense(cfg, meter, devices, *, lens, max_new, max_len, seed,
                probe_len):
    from repro.serving import FlowServeEngine

    print(f"phase A: {cfg.name} d={cfg.d_model} layers={cfg.num_layers} "
          f"vocab={cfg.vocab_size} ({cfg.param_count() / 1e9:.2f} B "
          "params, published widths)", flush=True)
    before, t0 = meter.snapshot(), time.perf_counter()
    engine = FlowServeEngine(cfg, n_dp_groups=2, max_batch=4,
                             max_len=max_len, seed=seed)
    rng = np.random.default_rng(seed)
    toks = prompts(rng, lens, cfg.vocab_size)
    toks.append(toks[0])                       # the repeated prompt
    reqs = serve(engine, toks, max_new)
    check_served(reqs, max_new, cfg.vocab_size, (0, len(toks) - 1))
    err = decode_vs_prefill(engine, toks[1], probe_len)
    check(err < DECODE_BAND, f"decode-step logits match prefill logits "
          f"at position {probe_len}: rel err {err:.5f} < {DECODE_BAND}")
    engine.close()
    report("A/dense", meter, before, time.perf_counter() - t0,
           sum(len(r.output_tokens) for r in reqs), devices)
    del engine
    gc.collect()


def phase_moe(cfg, meter, devices, *, lens, max_new, max_len, seed,
              chunk_T):
    import jax
    import jax.numpy as jnp

    from repro.kernels.gmm.ops import expert_ffn
    from repro.kernels.route_pack.ops import fused_route_pack
    from repro.kernels.route_pack.ref import route_pack_ref
    from repro.serving import FlowServeEngine

    e = cfg.moe
    print(f"phase B: {cfg.name} d={cfg.d_model} layers={cfg.num_layers} "
          f"experts={e.num_experts} top-{e.top_k} "
          f"({cfg.param_count() / 1e9:.2f} B params)", flush=True)
    before, t0 = meter.snapshot(), time.perf_counter()
    engine = FlowServeEngine(cfg, n_dp_groups=2, max_batch=2,
                             max_len=max_len, seed=seed)
    rng = np.random.default_rng(seed + 1)
    toks = prompts(rng, lens, cfg.vocab_size)
    toks.append(toks[0])
    reqs = serve(engine, toks, max_new)
    check_served(reqs, max_new, cfg.vocab_size, (0, len(toks) - 1))

    # the served decode program holds the compiled kernels
    be = engine.dps[0].backend
    B = engine.dps[0].max_batch
    cache = jax.eval_shape(lambda: be.init_cache(B, max_len))
    hlo = be._decode_sample.lower(
        be.params, cache, jnp.zeros((B, 1), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
        be._base_key, jnp.int32(0), None, stochastic=False).as_text()
    n_calls = hlo.count("tpu_custom_call")
    check(n_calls >= 2, f"decode step HLO holds {n_calls} tpu_custom_call "
          "ops (route_pack + gmm per scanned MoE layer)")

    # kernels against their references at the served shapes
    w = jax.tree.map(lambda a: a[0], engine.params["blocks"]["pos0"]["ffn"])
    E, d, k = e.num_experts, cfg.d_model, e.top_k
    for name, T in (("decode", B), ("prefill chunk", chunk_T)):
        C = max(int(T * k / E * e.capacity_factor), 4)
        kx = jax.random.fold_in(jax.random.PRNGKey(seed), T)
        x = jax.random.normal(kx, (E, C, d), jnp.float32).astype(cfg.dtype)
        args = (x, w["we_gate"], w["we_up"], w["we_down"])
        got = expert_ffn(*args, use_pallas=True)
        ref = expert_ffn(*args, use_pallas=False)
        err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
        check(err < GMM_BAND, f"Pallas gmm vs reference, {name} "
              f"[{E}, {C}, {d}]: rel err {err:.2e} < {GMM_BAND}")
    xs = jax.random.normal(jax.random.PRNGKey(seed), (chunk_T, d),
                           jnp.float32).astype(cfg.dtype)
    dest = jax.random.randint(jax.random.PRNGKey(seed + 1),
                              (chunk_T * k,), 0, E)
    cap = max(int(chunk_T * k / E * e.capacity_factor), 4)
    got = fused_route_pack(xs, dest, k=k, n_dest=E, capacity=cap,
                           use_pallas=True)
    ref = route_pack_ref(xs, dest, k=k, n_dest=E, capacity=cap)
    same = all(bool(jnp.array_equal(getattr(got, f), getattr(ref, f)))
               for f in ("buckets", "rank", "keep"))
    check(same, f"Pallas route_pack == reference, T={chunk_T} "
          f"[{E}, {cap}, {d}]")
    engine.close()
    report("B/moe", meter, before, time.perf_counter() - t0,
           sum(len(r.output_tokens) for r in reqs), devices)
    del engine
    gc.collect()


def phase_four_chips(cfg, meter, devices, *, lens, max_new, max_len, seed,
                     cut_layers):
    """EP-4 serving of ``cfg`` whole, then the ``cut_layers`` cut on the
    (1, 4) mesh against one chip."""
    import jax

    from repro.launch.sharding import param_shardings
    from repro.models.mesh_ctx import MeshCtx, make_auto_mesh, make_smoke_ctx
    from repro.serving import FlowServeEngine

    mesh = make_auto_mesh((1, 4), ("data", "model"), devices[:4])
    ctx = MeshCtx(mesh=mesh, batch_axes=("data",), remat="none")
    rng = np.random.default_rng(seed + 2)
    toks = prompts(rng, lens, cfg.vocab_size)
    toks.append(toks[0])

    print(f"4 chips: {cfg.name} whole ({cfg.num_layers} layers, "
          f"{cfg.moe.num_experts} experts, {cfg.param_count() / 1e9:.2f} B "
          "params), EP-4 on a (1, 4) data x model mesh", flush=True)
    before, t0 = meter.snapshot(), time.perf_counter()
    engine = FlowServeEngine(cfg, ctx=ctx, n_dp_groups=2, max_batch=2,
                             max_len=max_len, seed=seed)
    print("  params placed: bytes_in_use per chip "
          f"{mem(devices[:4], 'bytes_in_use')}", flush=True)
    ew = engine.params["blocks"]["pos0"]["ffn"]["we_gate"]
    n_local = {s.data.shape[1] for s in ew.addressable_shards}
    check(n_local == {cfg.moe.num_experts // 4},
          f"each chip holds {cfg.moe.num_experts // 4} of the "
          f"{cfg.moe.num_experts} experts")
    reqs = serve(engine, toks, max_new)
    failed = []
    try:
        check_served(reqs, max_new, cfg.vocab_size, (0, len(toks) - 1))
    except CheckFailed as e:
        # recorded, and raised once the EP-4 vs one-chip comparison has
        # run too
        failed.append(str(e))
        print(f"  FAILED: {e}: {reqs[0].output_tokens} vs "
              f"{reqs[-1].output_tokens}", flush=True)
    print(f"  determinism (max |logit diff|): "
          f"{determinism(engine, toks[0])}", flush=True)
    engine.close()
    report("4chip/ep4-whole", meter, before, time.perf_counter() - t0,
           sum(len(r.output_tokens) for r in reqs), devices[:4])
    # free the whole model's ~8 GB per chip before the float32 cut lands
    # on chip 0
    for leaf in jax.tree.leaves(engine.params):
        leaf.delete()
    del engine, ew, reqs
    gc.collect()
    print("  whole model freed: bytes_in_use per chip "
          f"{mem(devices[:4], 'bytes_in_use')}", flush=True)

    # the cut, in float32 and dropless on both layouts: the two layouts
    # size expert capacity differently, and bf16 rounding makes greedy
    # argmax of random weights a coin toss on near ties. ceil(E/k) is
    # the least factor whose per-expert capacity T*k/E*cf holds all T
    # tokens; EP-4 dispatch also needs cf >= 4 per destination rank
    e = cfg.moe
    dropless = float(max(math.ceil(e.num_experts / e.top_k), 4))
    cut = dataclasses.replace(
        cfg, num_layers=cut_layers, dtype="float32",
        moe=dataclasses.replace(e, capacity_factor=dropless))
    print(f"4 chips: {cut.name} cut to {cut_layers} layers, float32, "
          f"dropless capacity_factor={dropless}: EP-4 vs one chip",
          flush=True)
    before, t0 = meter.snapshot(), time.perf_counter()
    probe = toks[1]
    outs, logits = {}, {}
    with jax.default_matmul_precision("highest"):
        one = FlowServeEngine(cut, ctx=make_smoke_ctx(), n_dp_groups=2,
                              max_batch=2, max_len=max_len, seed=seed)
        params1 = one.params
        outs["one"] = [r.output_tokens for r in serve(one, toks, max_new)]
        _, logits["one"] = one.dps[0].backend.prefill_chunk(
            None, probe, 0, len(probe))
        one.close()
        del one
        ep_params = jax.device_put(
            params1, param_shardings(params1, mesh))
        del params1
        gc.collect()
        ep = FlowServeEngine(cut, params=ep_params, ctx=ctx, n_dp_groups=2,
                             max_batch=2, max_len=max_len, seed=seed)
        outs["ep4"] = [r.output_tokens for r in serve(ep, toks, max_new)]
        _, logits["ep4"] = ep.dps[0].backend.prefill_chunk(
            None, probe, 0, len(probe))
        ep.close()
    err = float(np.max(np.abs(logits["ep4"] - logits["one"]))
                / np.max(np.abs(logits["one"])))
    check(err < EP_BAND, f"prefill logits EP-4 vs one chip: rel err "
          f"{err:.2e} < {EP_BAND}")
    agree = [next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                  len(a)) for a, b in zip(outs["ep4"], outs["one"])]
    check(outs["ep4"] == outs["one"],
          f"greedy tokens EP-4 == one chip ({len(toks)} requests x "
          f"{max_new} tokens; leading tokens equal: {agree})")
    report("4chip/ep4-vs-1chip", meter, before, time.perf_counter() - t0,
           2 * len(toks) * max_new, devices[:4])
    del ep, ep_params
    gc.collect()
    if failed:
        raise CheckFailed("; ".join(failed))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the EP-4 deepseek-moe-16b path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={platform!r})",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    ds = get_config("deepseek-moe-16b")
    if args.four_chips:
        phase_four_chips(ds, meter, devices, lens=(480, 400, 300),
                         max_new=16, max_len=640, seed=args.seed,
                         cut_layers=4)
    else:
        phase_dense(get_config("internlm2-1.8b"), meter, devices,
                    lens=(1000, 900, 777, 640, 530, 230, 150),
                    max_new=32, max_len=1152, seed=args.seed,
                    probe_len=700)
        cut = dataclasses.replace(ds, num_layers=4)
        print(f"reduced: {ds.name} num_layers {ds.num_layers} -> 4 "
              "(1 dense + 3 MoE), widths as published", flush=True)
        phase_moe(cut, meter, devices, lens=(1000, 600, 300), max_new=32,
                  max_len=1152, seed=args.seed, chunk_T=1024)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

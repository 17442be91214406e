"""Quickstart: serve a smoke model end-to-end with FlowServe.

    PYTHONPATH=src python examples/quickstart.py [--arch internlm2-1.8b]

Spins up a FlowServe engine (decentralized DP groups + TE-shell), submits
a few requests, and streams tokens through the output-shortcutting path.
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import FlowServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--dp-groups", type=int, default=2)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch + "-smoke")
    print(f"arch={cfg.name} family={cfg.family} "
          f"params={cfg.param_count()/1e6:.1f}M (reduced smoke variant)")
    engine = FlowServeEngine(cfg, n_dp_groups=args.dp_groups,
                             max_batch=2, max_len=128)

    prompts = [
        "the expert dispatch routes tokens",
        "cloudmatrix has 384 chips",
        "prefill is compute bound, decode is memory bound",
    ]
    reqs = [engine.submit_text(p, args.max_new_tokens, ignore_eos=True)
            for p in prompts]
    engine.run_until_done()
    for r in reqs:
        text = engine.tokenizer.decode(r.output_tokens)
        print(f"[req {r.req_id}] ttft={r.ttft*1e3:.0f}ms "
              f"tpot={r.tpot*1e3:.1f}ms/token -> {text!r}")
    for dp in engine.dps:
        s = dp.status()
        print(f"[dp {s.dp_id}] kv_usage={s.kv_usage:.2f} "
              f"prefix_cache={len(dp.prefix_cache)} entries "
              f"gc_collections={dp.gc_ctl.collections}")
    engine.close()


if __name__ == "__main__":
    main()

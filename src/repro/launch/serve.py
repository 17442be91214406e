"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --smoke --mode pd-disagg --prompt "hello" --prompt "world"

``--full`` serves the published-width config (random weights);
internlm2-1.8b fits one TPU v5e chip.

Modes: ``colocated`` (single FlowServe TE) and ``pd-disagg`` (§5.1
pipeline: prefill TEs + decode TE over DistFlow).
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the published-width config")
    ap.add_argument("--mode", choices=["colocated", "pd-disagg"],
                    default="colocated")
    ap.add_argument("--prompt", action="append", default=None)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--dp-groups", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-len", type=int, default=256,
                    help="KV-cache length per slot (prompt + new tokens)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.configs import get_config
    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    prompts = args.prompt or ["hello from xdeepserve"]

    if args.mode == "colocated":
        from repro.serving import FlowServeEngine
        eng = FlowServeEngine(cfg, n_dp_groups=args.dp_groups,
                              max_batch=2, max_len=args.max_len)
        outs = eng.generate(prompts, args.max_new_tokens,
                            temperature=args.temperature)
        for p, o in zip(prompts, outs):
            print(f"{p!r} -> {o!r}")
        eng.close()
    else:
        from repro.core import DisaggregatedPD
        from repro.serving.request import Request
        pd = DisaggregatedPD(cfg, n_prefill_te=2, n_decode_te=1,
                             dp_per_te=args.dp_groups, max_batch=2,
                             max_len=args.max_len)
        reqs = [Request(prompt=p, max_new_tokens=args.max_new_tokens,
                        temperature=args.temperature, ignore_eos=True)
                for p in prompts]
        done = pd.run_until_done(reqs)
        tok = pd.tokenizer
        for r in sorted(done, key=lambda r: r.req_id):
            print(f"{r.prompt!r} (p{r.prefill_te}->d{r.decode_te}) -> "
                  f"{tok.decode(r.output_tokens)!r}")
        pd.close()


if __name__ == "__main__":
    main()

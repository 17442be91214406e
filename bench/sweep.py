"""The knee of an open-loop cell: one engine, one warm-up, then the
cell's mix at each of several fixed rates in turn, on the chip.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6 --seconds 20

For each rate, after the engine has drained the last one: an unrecorded
stretch of the mix's ``warm_seconds``, then a window of ``--seconds``.
One JSON line per rate: requests due and finished, TTFT and inter-token
percentiles, how late the client ran, and the backlog at the close. A
rate the system sustains finishes about what falls due and ends with
a backlog that does not grow. The cell's rate is then set at about four
fifths of the highest such rate. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import client as C                     # noqa: E402
from bench import model as bmodel                 # noqa: E402
from bench import run as R                        # noqa: E402
from bench import warmup                          # noqa: E402


def drain(engine) -> None:
    while C.busy(engine):
        engine.step()
        C.collect_finished(engine)


def at_rate(engine, mix: dict, rate: float, seed: int, seconds: float,
            vocab: int) -> dict:
    mix = copy.deepcopy(mix)
    mix["arrival"]["rate"] = rate
    warm = float(mix["warm_seconds"])
    client = C.Client(engine, mix, seed, vocab, warm + seconds + 1.0)
    client.start()
    t0 = client.t_start + warm
    t1 = t0 + seconds
    try:
        client.run_until(t1)
    except Exception as e:          # the engine's own failure ends the sweep
        return {"rate": rate, "error": f"{type(e).__name__}: {e}"}
    sent = [s for s in client.sent if t0 <= s.due < t1]
    ttft = C.ttft_sample(client.sent, t0, t1)
    itl = C.itl_sample(client.sent, t0, t1)
    out = {"rate": rate, "due": len(sent),
           "finished": sum(1 for s in sent
                           if s.req.state.name == "FINISHED"),
           "backlog_at_close": sum(1 for s in client.sent
                                   if s.req.state.name != "FINISHED"),
           "late_max_ms": max((s.sent - s.due for s in sent),
                              default=0.0) * 1e3,
           "tokens_per_s": C.tokens_in(client.sent, t0, t1) / seconds}
    for name, vals, qs in (("ttft_ms", ttft, (50, 90)),
                           ("itl_ms", itl, (50, 95))):
        out[name] = {q: C.percentile(vals, q) * 1e3 for q in qs} \
            if vals else {}
    drain(engine)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        R.devices_or_raise(cell.chips)
    except R.NoDevice as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    _, _, engine = bmodel.build_engine(cell.config, args.seed)
    vocab = cell.config["vocab_size"]
    warmup.warm(engine, cell.mix, vocab)
    for rate in (float(r) for r in args.rates.split(",")):
        rec = {"workload": args.workload,
               **at_rate(engine, cell.mix, rate, args.seed, args.seconds,
                         vocab)}
        print(json.dumps(rec), flush=True)
        if "error" in rec:
            break
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

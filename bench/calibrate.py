"""Readings that set the limit of ``correct``, per cell, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 20 [--out FILE]

For each seed: the weights and the traffic of that seed are served
through the cell's timed path (engine, widths, load) for ``--seconds``,
with no warm-up (compiles in the window do not matter here), and the
benchmark's own sample of finished requests is run through the
reference. The program's reading is the widest gap of its served
tokens. On the control seeds the same prompts and tokens are also read
by the control (the reference computed at fp8's precision,
``reference.py``) and by the reference computed at bfloat16's (a
witness beside the program): the widest gap of the tokens each puts
first. One JSON line per seed; the
benchmark's own runs never run this.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2 --seconds 30 \
        --fault half_batch

With ``--fault`` each seed is instead a whole run of the cell
(``run.run``: warm-up, the mix, the window, the benchmark's own sample
and limits) with that fault of ``faults.py`` planted under the timed
path; the line holds the numbers compared and whether the run came out
correct.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import client as C                     # noqa: E402
from bench import correctness, faults, weights    # noqa: E402
from bench import model as bmodel                 # noqa: E402
from bench import run as R                        # noqa: E402


def reading(cell: R.Cell, seed: int, seconds: float, control: bool,
            own_process: bool = True) -> dict:
    """One seed's readings: the cell's traffic served for ``seconds``,
    then :func:`judge` (``own_process`` False in tests, which share
    their process: see ``run.free_device_state``)."""
    conf = cell.config
    model, _, engine = bmodel.build_engine(conf, seed)
    rec = C.Recorder(engine)
    client = C.Client(engine, cell.mix, seed, conf["vocab_size"],
                      seconds + 1.0)
    client.start()
    client.run_until(client.t_start + seconds)
    for d in engine.dps:
        d.drain()
    done = correctness.finished(client.sent, rec.placed)
    engine.close()
    del engine, client, rec
    R.free_device_state(own_process)
    return judge(conf, model, seed, done, control, own_process)


def judge(conf: dict, model, seed: int, done, control: bool,
          own_process: bool = True) -> dict:
    """The benchmark's sample of the finished requests ``done`` read by
    the reference: the program's served tokens, and with ``control`` the
    tokens the fp8 control and the bf16 witness put first."""
    picked = correctness.sample(done, seed, R.SAMPLE_REQUESTS)
    out = {"seed": seed, "finished": len(done), "requests": len(picked),
           "slots": len({(p.dp, p.slot) for p in picked}),
           "tokens": sum(len(p.tokens) for p in picked)}
    if picked:
        params = weights.make(model, seed)
        out["program"] = correctness.readings(params, conf, picked)
        if control:
            for rounding in ("fp8", "bf16"):
                out[f"reference_{rounding}"] = correctness.readings(
                    params, conf, picked, rounding)
        del params
        R.free_device_state(own_process)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--fault", default=None, choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        devices = R.devices_or_raise(cell.chips)
    except R.NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    if args.fault:
        faults.plant(args.fault)
    for seed in seeds + sorted(ctl - set(seeds)):
        if args.fault:
            res = R.run(cell, seed, args.seconds, False, devices)
            rec = {"workload": args.workload, "fault": args.fault,
                   "seed": seed, "correct": res["correct"],
                   "compared": res["compared"]}
        else:
            rec = {"workload": args.workload,
                   **reading(cell, seed, args.seconds, seed in ctl)}
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

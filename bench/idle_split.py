"""One traced run of a cell, with the device's idle time split by the
program's own spans.

    python3 bench/idle_split.py --workload <cell> --seed <n> --seconds <s>
        [--python-tracer 0]

The run is ``bench/run.py --trace 1``'s, and its result line is printed
first. Then one JSON line:

* ``idle_s``: idle device seconds in the traced window;
* ``idle_by_span``: those seconds by the innermost program span open at
  each gap's midpoint (``none`` where no span was open), and
  ``below_step_share``: the share that fell in a span below
  ``engine.step``;
* ``spans``: spans recorded in the window, by name (count, mean us, sum
  of their counters ``n``: blocks sliced for ``dp.prefill.insert``);
* ``prefill_chunks_per_request``: ``dp.prefill`` spans that computed
  tokens, per request (by the spans' ``req_id``);
* ``ttft_split_ms``: the mean time to first token of the requests due in
  the window that have their first token, and its parts from the
  request's stamps: ``late`` (due to ``t_submit``: the client sends
  between engine steps), ``queue`` (to ``t_prefill_start``),
  ``prefill`` (to ``t_prefill_done``), ``admit_wait`` (to ``t_admit``)
  and ``first_token`` (to ``t_first_token``).

``--python-tracer 0`` runs the profiler without its Python tracer, which
otherwise records every Python call and so lengthens the host spans. A
program without its own spans gives an empty split. The benchmark's
runs never run this.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax                                        # noqa: E402

from bench import run as R                        # noqa: E402
from bench import spans as BS                     # noqa: E402
from bench import xplane                          # noqa: E402

STEP = "engine.step"

#: the request's stamps, in order, and the part of TTFT each one closes
STAMPS = (("t_submit", "late"), ("t_prefill_start", "queue"),
          ("t_prefill_done", "prefill"), ("t_admit", "admit_wait"),
          ("t_first_token", "first_token"))


def ttft_split(run) -> dict:
    """Mean TTFT (ms) of the requests due in the window whose first
    token came in it, and its parts between the request's stamps."""
    parts = collections.defaultdict(float)
    n = 0
    for s in run.sent:
        req = s.req
        times = [getattr(req, stamp, None) for stamp, _ in STAMPS]
        if not run.t0 <= s.due < run.t1 or None in times \
                or times[-1] >= run.t1:
            continue
        n += 1
        for (_, part), a, b in zip(STAMPS, [s.due] + times, times):
            parts[part] += b - a
        parts["ttft"] += times[-1] - s.due
    return {k: v / n * 1e3 for k, v in parts.items()} if n else {}


def split(run) -> dict:
    idle = BS.idle_by_path(run) or {}
    by_span = collections.defaultdict(float)
    for path, secs in idle.items():
        by_span[path[-1] if path else "none"] += secs
    total = sum(idle.values())
    below = sum(v for p, v in idle.items() if STEP in p and p[-1] != STEP)
    lo, hi = run.trace.window
    stats = collections.defaultdict(list)
    chunks = collections.Counter()
    for name, start, end, _, req_id, n in BS.program_spans(run) or ():
        if lo <= start < hi:
            stats[name].append((end - start, n))
            if name == "dp.prefill" and n > 0:
                chunks[req_id] += 1
    return {
        "idle_s": run.trace.window_s - xplane.busy_s(run.trace),
        "idle_by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "below_step_share": below / total if total else None,
        "spans": {k: [len(v), sum(d for d, _ in v) / len(v) / 1e3,
                      sum(n for _, n in v)]
                  for k, v in sorted(stats.items())},
        "prefill_chunks_per_request":
            sum(chunks.values()) / len(chunks) if chunks else None,
        "ttft_split_ms": ttft_split(run),
    }


def traced_run(cell, seed: int, seconds: float, devices=None,
               rehearsal: bool = False, python_tracer: bool = True):
    """``bench.run.run`` traced, keeping what its metric readers see;
    returns its result and the split. ``python_tracer=False`` starts the
    profiler without its Python tracer."""
    runs = []
    reader = R.reader
    start_trace = jax.profiler.start_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0

    def reader_keeping_run(name):
        read = reader(name)

        def keep(run):
            runs.append(run)
            return read(run)
        return keep

    R.reader = reader_keeping_run
    if not python_tracer:
        jax.profiler.start_trace = lambda log_dir: start_trace(
            log_dir, profiler_options=options)
    try:
        result = R.run(cell, seed, seconds, True, devices, rehearsal)
    finally:
        R.reader = reader
        jax.profiler.start_trace = start_trace
    return result, split(runs[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    try:
        devices = R.devices_or_raise(cell.chips)
    except R.NoDevice as e:
        print(f"idle_split: {e}", file=sys.stderr)
        return 2
    result, parts = traced_run(cell, args.seed, args.seconds, devices,
                               python_tracer=bool(args.python_tracer))
    print(json.dumps(result), flush=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "python_tracer": args.python_tracer, **parts}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

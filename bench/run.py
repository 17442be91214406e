"""Benchmark of the FlowServe serving path on the chip, one cell a run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``). The run makes the weights from the seed
on the device, builds ``FlowServeEngine`` at the configuration's
widths, compiles every program shape the mix can reach, runs an
unrecorded stretch of the same traffic, and then measures for
``--seconds``. With ``--trace 0`` the last line of stdout reports the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the line reports the per-layer metrics, each read by its
own reader ``bench/metrics/<metric>.py``. Either way the served tokens
of a sample of finished requests are then checked against the float32
reference (``correctness.py``), and each number compared is printed
beside its limit, last on stderr and last in the result line.

The run exits non-zero and prints no result when JAX finds no TPU,
fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                   # noqa: E402
import dataclasses                                # noqa: E402
import gc                                         # noqa: E402
import glob                                       # noqa: E402
import importlib.util                             # noqa: E402
import json                                       # noqa: E402
import shutil                                     # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402
from pathlib import Path                          # noqa: E402
from typing import Dict, List, Optional           # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import client as C                     # noqa: E402
from bench import correctness, warmup, weights, xplane  # noqa: E402
from bench import model as bmodel                 # noqa: E402
from bench.roofline import peaks as peak_table    # noqa: E402
from bench.traffic.generator import load_mix      # noqa: E402

BENCHMARK = ROOT / "BENCHMARK.json"
METRICS = Path(__file__).resolve().parent / "metrics"
#: finished requests the reference checks: the longest, then others
#: drawn from the seed over both DP groups and many decode slots
SAMPLE_REQUESTS = 16


class NoDevice(RuntimeError):
    pass


class CompileMeter:
    """Sums JAX's own compile events: XLA compiles (a persistent-cache
    hit counts only its read), their seconds, lowering seconds and
    persistent-cache hits (``chip_smoke.CompileMeter``, copied, with the
    count of compiles added)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.backend_s = self.lower_s = 0.0
        self.cache_hits = 0

        def duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.backend_s += secs
            elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lower_s += secs

        def count(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(count)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.backend_s,
                "lower_s": self.lower_s, "cache_hits": self.cache_hits}


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench or json.loads(BENCHMARK.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {BENCHMARK.name}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, int(w["chips"]), bmodel.load(ROOT / conf["file"]),
                load_mix(w["traffic"]),
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def devices_or_raise(chips: int):
    """The chips to run on; raises :class:`NoDevice` where there is no
    TPU, too few chips, or a device kind with no peaks."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    try:
        peak_table(devs[0].device_kind)
    except KeyError as e:
        raise NoDevice(str(e)) from None
    return devs[:chips]


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader sees."""
    conf: dict
    t0: float
    t1: float
    sent: List[C.Sent]
    rec: C.Recorder
    trace: Optional[xplane.Trace]
    peak: Optional[dict]


def reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, or, for a
    metric split by cell kind (``name.batch``), ``metrics/<base>.py``."""
    path = METRICS / f"{name}.py"
    if not path.is_file():
        path = METRICS / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(name: str, sent, t0: float, t1: float, setup_s: float):
    if name == "setup_s":
        return setup_s
    if name == "output_tokens_per_s":
        return C.tokens_in(sent, t0, t1) / (t1 - t0)
    if name.startswith("ttft_p"):
        q = float(name[len("ttft_p"):].split("_")[0])
        return C.percentile(C.ttft_sample(sent, t0, t1), q) * 1e3
    if name.startswith("tpot_p"):
        q = float(name[len("tpot_p"):].split("_")[0])
        return C.percentile(C.tpot_sample(sent, t0, t1), q) * 1e3
    raise KeyError(f"no end-to-end metric {name!r}")


def free_device_state(everything: bool) -> None:
    """Drop the program's state. In a process of its own the run deletes
    every array left on the device, so the reference starts from an
    empty chip; a test shares its process and only collects garbage."""
    import jax

    gc.collect()
    if everything:
        for a in jax.live_arrays():
            a.delete()
        gc.collect()


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices=None,
        rehearsal: bool = False) -> dict:
    """One run of ``cell``. ``rehearsal`` (tests, on the CPU) reports
    counts only, never numbers under the names of device metrics."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if not rehearsal:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter()
    conf, mix = cell.config, cell.mix
    vocab = conf["vocab_size"]
    marks: Dict[str, float] = {"start": C.clock()}

    model, params, engine = bmodel.build_engine(conf, seed)
    jax.block_until_ready(params)
    marks["engine"] = C.clock()
    rec = C.Recorder(engine)
    n_warm_calls = warmup.warm(engine, mix, vocab)
    marks["warm_programs"] = C.clock()
    warm_s = float(mix["warm_seconds"])
    client = C.Client(engine, mix, seed, vocab, warm_s + seconds + 5.0)
    client.start()
    client.run_until(client.t_start + warm_s)

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    window = None
    if trace:
        jax.profiler.start_trace(tmp)
        window = jax.profiler.TraceAnnotation(xplane.WINDOW)
        window.__enter__()
        rec.traced = True
    before = meter.snapshot()
    t0 = C.clock()
    setup_s = t0 - T_START
    t1 = t0 + seconds
    client.run_until(t1)
    in_window = meter.snapshot()["compiles"] - before["compiles"]
    if trace:
        rec.traced = False
        window.__exit__(None, None, None)
        jax.block_until_ready([d.cache for d in engine.dps])
        jax.profiler.stop_trace()

    for d in engine.dps:
        d.drain()
    stats = [dev.memory_stats() or {} for dev in (devices or [])]
    peaks_b = [s.get("peak_bytes_in_use") for s in stats]
    mem_peak = max(peaks_b) if peaks_b and None not in peaks_b else None

    sent = client.sent
    window_sent = [s for s in sent if t0 <= s.due < t1]
    failed = sum(1 for s in window_sent
                 if s.req.error is not None or s.req.state.name == "FAILED")
    done = correctness.finished(sent, rec.placed)
    peak = peak_table(devices[0].device_kind) if devices else None

    red = None
    if trace:
        path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        red = xplane.load(path, [name for _, _, name in C.SPANS])
        shutil.rmtree(tmp, ignore_errors=True)
    data = RunData(conf, t0, t1, sent, rec, red, peak)
    metrics: Dict[str, dict] = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        if trace:
            value = reader(m["name"])(data)
        else:
            value = end_to_end(m["name"], sent, t0, t1, setup_s)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    detail = {
        "requests_sent_in_window": len(window_sent),
        "requests_finished": len(done),
        "late_max_ms": max((s.sent - s.due for s in window_sent),
                           default=0.0) * 1e3,
        "compiles_in_window": in_window,
        "setup": {"process_s": marks["start"] - T_START,
                  "weights_and_engine_s": marks["engine"] - marks["start"],
                  "warm_programs_s": marks["warm_programs"]
                  - marks["engine"],
                  "warm_calls": n_warm_calls,
                  "warm_traffic_s": t0 - marks["warm_programs"],
                  **before},
        "decode_launches": sum(1 for x in rec.launches if t0 <= x.t < t1),
        "steps": sum(1 for s in rec.steps if t0 <= s[0] < t1),
    }
    if not trace:
        for key, vals, qs in (
                ("ttft_ms", C.ttft_sample(sent, t0, t1), (50, 90, 99)),
                ("tpot_ms", C.tpot_sample(sent, t0, t1), (50, 90, 99)),
                ("itl_ms", C.itl_sample(sent, t0, t1), (50, 95, 99))):
            detail[key] = {q: C.percentile(vals, q) * 1e3 for q in qs} \
                if vals else {}
            detail["n_" + key[:-3]] = len(vals)
    print("detail " + json.dumps(detail), flush=True)

    engine.close()
    del engine, rec, client, params, data
    free_device_state(not rehearsal)

    t_ref = C.clock()
    picked = correctness.sample(done, seed, SAMPLE_REQUESTS)
    read = {}
    if picked:
        params = weights.make(model, seed)
        read = correctness.readings(params, conf, picked)
        del params
        free_device_state(not rehearsal)
    compared = {k: {"value": read.get(k), "limit": v}
                for k, v in conf["limits"].items()}
    compared["failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())
    print(f"reference: {len(picked)} requests on "
          f"{len({(p.dp, p.slot) for p in picked})} slots of "
          f"{len({p.dp for p in picked})} DP groups, "
          f"{sum(len(p.tokens) for p in picked)} served tokens, "
          f"{C.clock() - t_ref:.1f} s", flush=True)

    d0 = (devices or jax.devices())[0]
    result = {
        "correct": bool(correct),
        "attempted": len(window_sent),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices or [d0]),
                   "memory_peak_bytes": mem_peak},
    }
    if trace:
        result["device"]["busy_s"] = xplane.busy_s(red)
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": xplane.top_ops(red),
                               "idle_gaps": xplane.attribute_idle(red)}
    if rehearsal:
        result["rehearsal"] = {"metrics_read": sorted(metrics),
                               "tokens_in_window": C.tokens_in(sent, t0, t1),
                               "compiles_in_window": in_window,
                               "warm_calls": n_warm_calls}
        result["metrics"] = {}
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        devices = devices_or_raise(cell.chips)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for k, v in result["compared"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

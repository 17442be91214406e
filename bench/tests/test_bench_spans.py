"""The program's spans on the trace's clock, and the readers of idle time
and queue wait, on a small synthetic trace."""
import glob
import time
import types

import jax
import pytest

from bench import run as R
from bench import spans as BS
from bench import xplane
from bench.tests.test_bench_xplane import _plane
from repro.serving.spans import RECORDER, Span, span

T0 = 5.0                       # run.t0 on the client's clock (s)
T0_NS = int(T0 * 1e9)

#: program spans on the trace clock (ns): (name, start, end, parent, n)
PROGRAM = [
    ("engine.step", 0, 8800, None, 3),
    ("dp.prefill", 100, 3500, "engine.step", 64),
    ("backend.prefill.fetch", 2500, 3400, "dp.prefill", 0),
    ("dp.prefill", 3600, 3700, "engine.step", 0),       # chunk skipped
    ("dp.decode.launch", 5500, 6500, "engine.step", 2),
    ("dp.decode.inputs", 5600, 5700, "dp.decode.launch", 0),
    ("backend.decode.dispatch", 5800, 6400, "dp.decode.launch", 0),
    ("dp.decode.launch", 8600, 8700, "engine.step", 2),
]


class FakeRecorder:
    def __init__(self, spans):
        self.spans = spans

    def between(self, t0_ns, t1_ns):
        return [s for s in self.spans
                if s.end_ns > t0_ns and s.start_ns < t1_ns]


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    op = "%fusion.1 = bf16[8] fusion(%p)"
    # busy [1000,2000) [4000,5000) [7000,8000); idle gaps at midpoints
    # 500 (dp.prefill), 3000 (its fetch), 6000 (the decode dispatch)
    # and 9000 (no span)
    device = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit__step(1)", 1000, 1000),
                        ("jit_prefill_chunk(2)", 4000, 1000),
                        ("jit__step(1)", 7000, 1000)],
        "XLA Ops": [(op, 1000, 1000), (op, 4000, 1000), (op, 7000, 1000)],
    })
    host = _plane(2, "/host:CPU", {"python": [("bench.window", 0, 10000)]})
    return xplane.load(ProfileData.from_text_proto(device + "\n" + host))


@pytest.fixture
def program(monkeypatch):
    spans = [Span(n, T0_NS + s, T0_NS + e, p, 1 if "prefill" in n else None,
                  k) for n, s, e, p, k in PROGRAM]
    monkeypatch.setattr(BS, "_recorder", lambda: FakeRecorder(spans))


def _run(trace, sent=()):
    return types.SimpleNamespace(t0=T0, t1=T0 + 10e-6, trace=trace,
                                 sent=list(sent))


def test_idle_gaps_go_to_the_chain_of_open_program_spans(trace, program):
    idle = BS.idle_by_path(_run(trace))
    step = ("engine.step",)
    assert idle == pytest.approx({
        step + ("dp.prefill",): 1000e-9,
        step + ("dp.prefill", "backend.prefill.fetch"): 2000e-9,
        step + ("dp.decode.launch", "backend.decode.dispatch"): 2000e-9,
        (): 2000e-9})
    assert sum(idle.values()) == pytest.approx(
        trace.window_s - xplane.busy_s(trace))


def test_idle_per_prefill_chunk_and_per_launch(trace, program):
    run = _run(trace)
    # 3,000 ns under dp.prefill over the one chunk that computed tokens
    for name in ("prefill_idle_ms.batch", "prefill_idle_ms.serve"):
        assert R.reader(name)(run) == pytest.approx(3000e-6)
    # 2,000 ns under dp.decode.launch over two launches
    for name in ("launch_idle_ms.batch", "launch_idle_ms.serve"):
        assert R.reader(name)(run) == pytest.approx(1000e-6)


@pytest.mark.parametrize("name", ["prefill_idle_ms.serve",
                                  "launch_idle_ms.batch"])
def test_idle_readers_stay_silent_without_spans_or_devices(
        trace, name, monkeypatch):
    read = R.reader(name)
    assert read(_run(None)) is None
    monkeypatch.setattr(BS, "_recorder", lambda: FakeRecorder([]))
    assert read(_run(trace)) is None
    # a program without its own spans, as before they were added
    monkeypatch.setattr(BS, "_recorder", lambda: None)
    assert read(_run(trace)) is None


def test_queue_wait_median_counts_unstarted_requests_at_the_close():
    def sent(due, start):
        return types.SimpleNamespace(
            due=due, req=types.SimpleNamespace(t_prefill_start=start))

    run = types.SimpleNamespace(t0=100.0, t1=110.0, trace=None, sent=[
        sent(99.0, 99.5),              # due before the window: left out
        sent(101.0, 101.010),          # 10 ms
        sent(102.0, 102.030),          # 30 ms from due, however late sent
        sent(103.0, 110.5),            # started after t1: 7,000 ms
        sent(109.9, None)])            # not started: 100 ms
    read = R.reader("queue_wait_p50_ms")
    assert read(run) == pytest.approx(65.0)
    run.sent = run.sent[:1]
    assert read(run) is None
    # a program that stamps no prefill start
    old = types.SimpleNamespace(due=101.0, req=types.SimpleNamespace(
        t_arrival=101.0))
    run.sent = [old]
    assert read(run) is None


def test_program_spans_land_on_their_own_trace_events(tmp_path):
    """Each span's interval in memory, moved onto the trace clock by the
    window anchor, lies within 50 us of its TraceAnnotation event."""
    names = ["engine.step", "dp.prefill", "backend.prefill.dispatch",
             "dp.decode.launch"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("warm-up"):
            pass
        RECORDER.clear()
        # as the harness opens its window: the event, then t0
        window = jax.profiler.TraceAnnotation(xplane.WINDOW)
        window.__enter__()
        t0 = time.perf_counter()
        for _ in range(2):
            with span("engine.step"):
                with span("dp.prefill", req_id=3):
                    with span("backend.prefill.dispatch"):
                        time.sleep(0.002)
                with span("dp.decode.launch"):
                    time.sleep(0.001)
        window.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    trace = xplane.load(path, names)
    mapped = BS.program_spans(types.SimpleNamespace(t0=t0, trace=trace))
    assert sorted(s[0] for s in mapped) == sorted(trace.host.name)
    events = sorted(zip(trace.host.start.tolist(), trace.host.end.tolist(),
                        trace.host.name))
    for (name, start, end, *_), (e0, e1, ename) in zip(
            sorted(mapped, key=lambda s: s[1]), events):
        assert name == ename
        assert abs(start - e0) < 50_000 and abs(end - e1) < 50_000


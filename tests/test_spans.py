"""The serving path's span recorder: silent without a profiler session;
under one it keeps each span's nesting, request and counter."""
import threading

import jax
import pytest

from repro.serving.spans import RECORDER, SpanRecorder, span

EVERYTHING = (0, 2**63 - 1)


@pytest.fixture
def profiling(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def test_nothing_records_without_a_profiler_session():
    RECORDER.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with span("engine.step") as sp:
        sp.n = 3
        with span("dp.prefill", req_id=4) as inner:
            inner.n += 1
    assert span("a") is span("b")        # one shared no-op context
    assert RECORDER.between(*EVERYTHING) == []


def test_a_profiler_session_records_nesting_request_and_counter(profiling):
    RECORDER.clear()
    with span("engine.step") as step:
        with span("dp.prefill", req_id=7) as pre:
            pre.n = 96
            with span("backend.prefill.dispatch"):
                pass
        with span("dp.decode.launch") as launch:
            launch.n = 2
        step.n = 2
    got = {s.name: s for s in RECORDER.between(*EVERYTHING)}
    assert set(got) == {"engine.step", "dp.prefill",
                        "backend.prefill.dispatch", "dp.decode.launch"}
    assert got["engine.step"].parent is None
    assert got["dp.prefill"].parent == "engine.step"
    assert got["backend.prefill.dispatch"].parent == "dp.prefill"
    assert got["dp.decode.launch"].parent == "engine.step"
    # a child of a request's span concerns that request
    assert got["dp.prefill"].req_id == 7
    assert got["backend.prefill.dispatch"].req_id == 7
    assert got["engine.step"].req_id is None
    assert got["dp.decode.launch"].req_id is None
    assert (got["dp.prefill"].n, got["dp.decode.launch"].n,
            got["engine.step"].n) == (96, 2, 2)
    assert got["backend.prefill.dispatch"].n == 0
    outer, inner = got["engine.step"], got["backend.prefill.dispatch"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_each_thread_nests_its_own_spans(profiling):
    RECORDER.clear()
    with span("engine.step"):
        t = threading.Thread(target=lambda: span("dp.gc").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    got = {s.name: s for s in RECORDER.between(*EVERYTHING)}
    assert got["dp.gc"].parent is None


def test_the_buffer_is_bounded_and_selects_by_time(profiling):
    rec = SpanRecorder(capacity=4)
    for _ in range(6):
        with rec.span("x"):
            pass
    spans = rec.between(*EVERYTHING)
    assert len(spans) == 4
    mid = spans[1]
    assert rec.between(mid.start_ns, mid.end_ns) == [mid]
    assert rec.between(mid.end_ns, mid.end_ns + 1) == []
    rec.clear()
    assert rec.between(*EVERYTHING) == []


def test_an_insert_records_one_slice_span(make_model, tmp_path):
    """The radix insert slices every new block in one backend dispatch:
    one ``backend.prefill.slice`` under ``dp.prefill.insert``, counting
    the blocks the program materialises (the buffer's whole blocks),
    while the insert counts the blocks it stored."""
    import numpy as np

    from repro.serving.backend import JAXBackend
    from repro.serving.dp_group import DPGroup
    _, m, params = make_model("internlm2-1.8b")
    be = JAXBackend(m, params, max_len=256)
    dp = DPGroup(0, be, max_batch=2, max_len=256)
    try:
        toks = np.random.default_rng(0).integers(2, 60, 90).tolist()
        dp._cache_insert(toks[:40], be.prefill_chunk(None, toks[:40], 0,
                                                     40)[0])
        cache, _ = be.prefill_chunk(None, toks, 0, len(toks))  # 128 long
        be.slice_prefill_blocks(cache, toks, 0, 1, 16)          # compile
        RECORDER.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            dp._cache_insert(toks, cache)
        finally:
            jax.profiler.stop_trace()
    finally:
        dp.close()
    got = RECORDER.between(*EVERYTHING)
    assert [s.name for s in got] == ["backend.prefill.slice",
                                     "dp.prefill.insert"]
    sl, ins = got
    assert sl.parent == "dp.prefill.insert"
    assert sl.n == 128 // 16            # the buffer's whole blocks
    assert ins.n == 90 // 16 - 40 // 16  # the blocks not yet cached

"""CPU rehearsals of the open-loop cells, traced through
``bench/idle_split.py``: the queue wait reads from the program's request
stamps, the program records its spans in the window while the profiler
runs (the chat cell's without the profiler's Python tracer), and the
metrics read from the device trace stay silent on the CPU."""
import json

import jax
import pytest

from bench import client, idle_split, run
from bench.tests import tiny

#: spans every traced window of an open-loop cell holds
SERVED = {"engine.step", "shell.schedule", "dp.prefill", "dp.prefill.match",
          "backend.prefill.dispatch", "backend.prefill.fetch",
          "dp.prefill.insert", "dp.admit", "dp.decode.launch",
          "dp.decode.inputs", "backend.decode.dispatch",
          "dp.decode.complete", "dp.decode.fetch", "dp.decode.apply"}


@pytest.mark.parametrize("name,seed", [("internlm2-prefix", 2**31 + 13),
                                       ("internlm2-chat", 2**31 + 29)])
def test_traced_rehearsal_reads_queue_wait(name, seed, monkeypatch):
    # the cell as BENCHMARK.json commits it, not the tests' own chat cell
    monkeypatch.setattr(tiny, "EXTRA", {})
    cell = tiny.tiny_cell(name)
    committed = {w["name"]: w["traffic"] for w in
                 json.loads(run.BENCHMARK.read_text())["workloads"]}
    assert cell.mix["arrival"] == run.load_mix(committed[name])["arrival"]
    python_tracer = name != "internlm2-chat"
    reader, start_trace = run.reader, jax.profiler.start_trace
    res, split = idle_split.traced_run(cell, seed, 4.0, rehearsal=True,
                                       python_tracer=python_tracer)
    assert run.reader is reader
    assert jax.profiler.start_trace is start_trace
    assert res["correct"], res["compared"]
    reh = res["rehearsal"]
    assert reh["compiles_in_window"] == 0
    read = set(reh["metrics_read"])
    assert "queue_wait_p50_ms" in read
    device = {m["name"] for m in cell.per_layer
              if m["source"] == "device_trace"}
    assert "prefill_idle_ms.serve" in device
    assert not read & device
    assert SERVED <= set(split["spans"])
    assert split["spans"]["engine.step"][0] > 0
    # the radix insert counts the blocks it sliced
    assert split["spans"]["dp.prefill.insert"][2] > 0
    assert split["prefill_chunks_per_request"] >= 1
    # the parts of TTFT, stamp to stamp, add up to it
    ttft = split["ttft_split_ms"]
    assert set(ttft) == {"late", "queue", "prefill", "admit_wait",
                         "first_token", "ttft"}
    assert min(ttft.values()) >= 0
    assert sum(v for k, v in ttft.items() if k != "ttft") == \
        pytest.approx(ttft["ttft"])
    # no name the harness's own wrappers use: its reduction keeps host
    # events by name
    assert not set(split["spans"]) & {n for _, _, n in client.SPANS}
    # the CPU has no device trace to split
    assert split["idle_by_span"] == {}

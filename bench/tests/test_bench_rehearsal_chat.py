"""CPU rehearsal of a whole run of the chat cell at a small size, through
the harness's test entry: it reports counts, never numbers under the
names of device metrics."""
from bench import run
from bench.tests.tiny import tiny_cell


def test_chat_run_end_to_end():
    cell = tiny_cell("internlm2-chat")
    res = run.run(cell, 2**31 + 101, 4.0, trace=False, rehearsal=True)
    assert res["correct"], res["compared"]
    assert res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    reh = res["rehearsal"]
    assert reh["compiles_in_window"] == 0
    assert reh["tokens_in_window"] > 0
    assert set(reh["metrics_read"]) == {m["name"] for m in cell.end_to_end}
    assert list(res)[-1] == "compared"

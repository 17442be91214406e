"""CPU rehearsal of the closed-loop MoE batch cell."""
from bench import run
from bench.tests.tiny import tiny_cell


def test_batch_run_end_to_end():
    cell = tiny_cell("dsmoe16b-batch")
    res = run.run(cell, 5, 4.0, trace=False, rehearsal=True)
    assert res["correct"], res["compared"]
    assert res["metrics"] == {}
    assert res["rehearsal"]["compiles_in_window"] == 0
    assert res["rehearsal"]["tokens_in_window"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0

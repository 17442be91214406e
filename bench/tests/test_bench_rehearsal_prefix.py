"""CPU rehearsal of the prefix-sharing cell, traced: the counters that
need no device read, the device-trace metrics stay silent on the CPU,
and the warm-up leaves nothing to compile in the window."""
from bench import run
from bench.tests.tiny import tiny_cell


def test_prefix_run_traced():
    cell = tiny_cell("internlm2-prefix")
    res = run.run(cell, 7, 4.0, trace=True, rehearsal=True)
    assert res["correct"], res["compared"]
    assert res["metrics"] == {}
    reh = res["rehearsal"]
    assert reh["compiles_in_window"] == 0
    read = set(reh["metrics_read"])
    assert {"engine_step_ms.serve", "prefix_hit_share"} <= read
    assert not read & {"decode_step_ms.serve", "decode_roofline.serve",
                       "device_idle_share.serve", "step_mfu.serve"}
    assert "busy_s" in res["device"] and "window_s" in res["device"]

"""Share of the traced window in which no operation ran on the device
(1 - union of op intervals / window), averaged over chips.
Layer: device."""
from bench import xplane


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - xplane.busy_s(run.trace) / run.trace.window_s)

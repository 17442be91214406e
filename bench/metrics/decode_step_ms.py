"""Device time per call of the decode+sample program, from the trace.
Layer: backend programs."""
from bench import xplane
from bench.metrics import DECODE


def read(run):
    if run.trace is None:
        return None
    ev = xplane.modules(run.trace, DECODE)
    if not len(ev):
        return None
    return ev.total_s() / len(ev) * 1e3

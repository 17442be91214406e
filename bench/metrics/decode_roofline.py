"""Least time the chip needs for a decode step's required work
(``bench/roofline/decode_step.py``: weights once, the live context's
keys and values, live slots only) over the decode program's device
time, in the traced window. Layer: model."""
from bench import xplane
from bench.metrics import DECODE, traced_launches
from bench.roofline import decode_step, least_time


def read(run):
    if run.trace is None or run.peak is None:
        return None
    ev = xplane.modules(run.trace, DECODE)
    xs = traced_launches(run)
    if not len(ev) or not xs:
        return None
    need = sum(least_time(*decode_step.work(run.conf, x.n_live, x.ctx_sum),
                          run.peak) for x in xs)
    return 100.0 * (need / len(xs)) / (ev.total_s() / len(ev))

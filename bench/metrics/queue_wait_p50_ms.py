"""Median wait from a request's due time to the start of its first
prefill chunk (``Request.t_prefill_start``), over the requests due in
the window; one with no prefill start by ``t1`` enters at its wait so
far. The wait holds both queues a request meets before its prefill: the
engine loop's, since the client submits between engine steps as a front
end feeding the loop does, and the chunk scheduler's. Layer: scheduler."""
from bench import client as C


def read(run):
    waits = []
    for s in run.sent:
        if not run.t0 <= s.due < run.t1:
            continue
        if not hasattr(s.req, "t_prefill_start"):   # a program that
            return None                             # stamps no times
        start = s.req.t_prefill_start
        waits.append((start if start is not None and start < run.t1
                      else run.t1) - s.due)
    if not waits:
        return None
    return C.percentile(waits, 50) * 1e3

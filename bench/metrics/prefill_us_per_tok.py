"""Device time of the prefill-chunk programs per prompt token they
computed (prefix-cache hits excluded), over the traced window.
Layer: backend programs."""
from bench import xplane
from bench.metrics import PREFILL, traced_chunks


def read(run):
    if run.trace is None:
        return None
    ev = xplane.modules(run.trace, PREFILL)
    tokens = sum(c.end - c.start for c in traced_chunks(run))
    if not len(ev) or not tokens:
        return None
    return ev.total_s() / tokens * 1e6

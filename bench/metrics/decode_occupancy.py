"""Mean share of decode slots holding a request, over the decode
launches of the window (``DPGroup.slots`` read at each launch).
Layer: scheduler."""
from bench.metrics import in_window


def read(run):
    xs = [x for x in run.rec.launches if in_window(run, x.t)]
    if not xs:
        return None
    return 100.0 * sum(x.n_live / x.slots for x in xs) / len(xs)

"""Host time inside ``FlowServeEngine.step`` per step, over the window
(host clock around each call). Layer: engine loop."""


def read(run):
    steps = [(a, b) for a, b in run.rec.steps if run.t0 <= a < run.t1]
    if not steps:
        return None
    return sum(b - a for a, b in steps) / len(steps) * 1e3

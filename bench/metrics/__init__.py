"""Per-layer metric readers: ``<metric>.py`` defines ``read(run)`` and
returns the metric's value, or ``None`` where the run holds nothing to
read (the harness then leaves the metric out of the result line). A
metric split by cell kind (``<base>.batch``, ``<base>.serve``) reads
through ``<base>.py`` unless a file of its own exists.

``run`` is ``bench.run.RunData``: the window ``[t0, t1)`` on the host
clock, the client's requests, the recorder's counters (steps, decode
launches, prefill chunks), the reduced trace (``bench.xplane.Trace``,
``None`` without one) and the device's peaks.

Device programs are found by the names of the program's jitted
functions, as the trace's ``XLA Modules`` line gives them.
"""
from __future__ import annotations

#: module-name fragments of the served programs
DECODE = "jit__step"
PREFILL = "jit_prefill_chunk"
#: op-name fragment of the Pallas grouped expert FFN kernel
GMM = "gmm"


def in_window(run, t: float) -> bool:
    return run.t0 <= t < run.t1


def traced_launches(run):
    return [x for x in run.rec.launches if x.traced]


def traced_chunks(run):
    return [c for c in run.rec.chunks if c.traced]

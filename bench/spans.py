"""The program's own host spans (``repro.serving.spans``) on the trace's
clock, and the device's idle time attributed to them.

The program keeps its spans in memory on ``time.perf_counter_ns``, the
client's clock. The benchmark's window is the anchor between the two
clocks: the trace's window event opens at ``run.t0`` (to within a few
microseconds). Each idle gap of a device goes to the program spans open
at its midpoint, the innermost one first: the rule of
``xplane.attribute_idle``, here with the whole chain of open spans kept,
so a metric can sum a span with everything below it.

A program without the span recorder, or a run without a device trace,
gives ``None`` and the metrics that read it stay silent.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

from bench import xplane

#: the chain of program spans open at an idle gap, outermost first
Path = Tuple[str, ...]


def _recorder():
    try:
        from repro.serving.spans import RECORDER
    except ImportError:         # a program that records no spans
        return None
    return RECORDER


def program_spans(run) -> Optional[list]:
    """The program's spans that overlap the traced window, as
    ``(name, start, end, parent, req_id, n)`` on the trace clock
    (ns), in order of start; ``None`` where there are none."""
    rec = _recorder()
    if run.trace is None or rec is None:
        return None
    lo, hi = run.trace.window
    offset = lo - round(run.t0 * 1e9)
    spans = [(s.name, s.start_ns + offset, s.end_ns + offset, s.parent,
              s.req_id, s.n)
             for s in rec.between(lo - offset, hi - offset)]
    return sorted(spans, key=lambda s: s[1]) or None


def idle_by_path(run) -> Optional[Dict[Path, float]]:
    """Idle device seconds by the chain of program spans open at each
    gap's midpoint (``()`` where none was), averaged over devices."""
    spans = program_spans(run)
    trace = run.trace
    if spans is None or not trace.devices:
        return None
    tally: Dict[Path, float] = collections.defaultdict(float)
    for dev in trace.devices:
        mids = sorted(((s + e) // 2, e - s)
                      for s, e in xplane.idle_gaps(trace, dev))
        stack: List[Tuple[int, str]] = []
        j = 0
        for mid, dur in mids:
            while j < len(spans) and spans[j][1] <= mid:
                name, start, end = spans[j][:3]
                # spans of one thread nest: what closed before this one
                # opened is no ancestor of it
                while stack and stack[-1][0] <= start:
                    stack.pop()
                stack.append((end, name))
                j += 1
            while stack and stack[-1][0] <= mid:
                stack.pop()
            tally[tuple(n for _, n in stack)] += \
                dur * 1e-9 / len(trace.devices)
    return dict(tally)


def idle_under(run, name: str) -> Optional[float]:
    """Idle device seconds in which span ``name`` (or a span below it)
    was open."""
    idle = idle_by_path(run)
    if idle is None:
        return None
    return sum(v for path, v in idle.items() if name in path)


def counted(run, name: str) -> List[tuple]:
    """Spans ``name`` that opened in the traced window with a counter
    above zero."""
    lo, hi = run.trace.window
    return [s for s in program_spans(run) or ()
            if s[0] == name and lo <= s[1] < hi and s[5] > 0]


def per_span_ms(run, name: str) -> Optional[float]:
    """Idle device ms under span ``name``, per such span that counted
    work."""
    idle = idle_under(run, name)
    if idle is None:
        return None
    n = len(counted(run, name))
    return idle / n * 1e3 if n else None

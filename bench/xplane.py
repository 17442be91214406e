"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy and idle time, time per device
program and per device op, and idle gaps attributed to the host span
that was open while the device waited.

Planes whose name starts with ``/device:`` are devices. On each, the
``XLA Modules`` line holds one event per program execution (named
``jit_<function>(<hash>)``) and the ``XLA Ops`` line one event per
operation, named by its HLO text (``%gmm.7 = f32[...] custom-call(...)``);
an op belongs to the program execution that encloses it in time, and
is known by its short name (``gmm``: the instruction name less its
``%`` and numeric suffix). Busy time is the union of the op intervals.
Host spans are the events of the host planes named in ``span_names``;
the window is the host event named ``WINDOW`` (the benchmark opens it
around the traced stretch).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Events:
    """Events of one kind as parallel arrays (ns on the trace clock)."""
    name: List[str]
    module: List[str]
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def empty(cls) -> "Events":
        return cls([], [], np.zeros(0, np.int64), np.zeros(0, np.int64))

    def select(self, keep: Sequence[bool]) -> "Events":
        idx = [i for i, k in enumerate(keep) if k]
        return Events([self.name[i] for i in idx],
                      [self.module[i] for i in idx],
                      self.start[idx], self.end[idx])

    def total_s(self) -> float:
        return float(np.sum(self.end - self.start)) * 1e-9

    def __len__(self) -> int:
        return len(self.name)


@dataclasses.dataclass
class Device:
    name: str
    modules: Events
    ops: Events


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: List[Device]
    host: Events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def short_name(hlo_text: str) -> str:
    """``%gmm.7 = f32[...] custom-call(...)`` -> ``gmm``;
    ``jit__step(123)`` -> ``jit__step``."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    return _SUFFIX.sub("", head)


def _events(line, window: Tuple[int, int]) -> Events:
    names, starts, ends = [], [], []
    lo, hi = window
    for e in line.events:
        s, t = int(e.start_ns), int(e.end_ns)
        if t <= lo or s >= hi:
            continue
        names.append(short_name(e.name))
        starts.append(max(s, lo))
        ends.append(min(t, hi))
    return Events(names, [""] * len(names), np.asarray(starts, np.int64),
                  np.asarray(ends, np.int64))


def _enclosing(ops: Events, mods: Events) -> Events:
    """``ops`` with each op's module set to the program execution that
    encloses its start."""
    order = np.argsort(mods.start, kind="stable")
    starts = mods.start[order].tolist()
    ends = mods.end[order].tolist()
    names = [mods.name[i] for i in order]
    owner = []
    for s in ops.start.tolist():
        i = bisect.bisect_right(starts, s) - 1
        owner.append(names[i] if i >= 0 and s < ends[i] else "")
    return Events(ops.name, owner, ops.start, ops.end)


def load(profile, span_names: Iterable[str] = ()) -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` (or a path to an
    ``.xplane.pb``) to a :class:`Trace`, clipped to the window event."""
    from jax.profiler import ProfileData

    if not isinstance(profile, ProfileData):
        profile = ProfileData.from_file(str(profile))
    spans = set(span_names) | {WINDOW}
    host_events = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend(
                    (e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events if e.name in spans)
    wins = [(s, t) for n, s, t in host_events if n == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW!r} host event")
    window = (min(s for s, _ in wins), max(t for _, t in wins))
    host = [(n, s, t) for n, s, t in host_events
            if n != WINDOW and t > window[0] and s < window[1]]
    host_ev = Events([n for n, _, _ in host], [""] * len(host),
                     np.asarray([max(s, window[0]) for _, s, _ in host],
                                np.int64),
                     np.asarray([min(t, window[1]) for _, _, t in host],
                                np.int64))
    devices = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = (_events(lines["XLA Modules"], window)
                if "XLA Modules" in lines else Events.empty())
        ops = _enclosing(_events(lines["XLA Ops"], window), mods)
        devices.append(Device(plane.name, mods, ops))
    return Trace(window, devices, host_ev)


def merge(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[int, int]]:
    """Union of intervals, as sorted disjoint ``(start, end)`` pairs."""
    out: List[List[int]] = []
    for s, t in sorted(zip(starts.tolist(), ends.tolist())):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over devices."""
    if not trace.devices:
        return 0.0
    per = [sum(t - s for s, t in merge(d.ops.start, d.ops.end))
           for d in trace.devices]
    return float(np.mean(per)) * 1e-9


def idle_gaps(trace: Trace, device: Device) -> List[Tuple[int, int]]:
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in merge(device.ops.start, device.ops.end):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_idle(trace: Trace, top: int = 10) -> List[List]:
    """Idle device time by what the host was doing: each gap goes to the
    innermost host span open at its midpoint (``client`` where no engine
    span was open: the client waiting for arrivals or keeping time).
    Summed per span and averaged over devices, largest first."""
    host = trace.host
    order = np.argsort(host.start, kind="stable")
    spans = [(int(host.start[i]), int(host.end[i]), host.name[i])
             for i in order]
    tally: Dict[str, float] = collections.defaultdict(float)
    for dev in trace.devices:
        gaps = sorted(((s + e) // 2, e - s) for s, e in idle_gaps(trace, dev))
        stack: List[Tuple[int, str]] = []
        j = 0
        for mid, dur in gaps:
            while j < len(spans) and spans[j][0] <= mid:
                stack.append((spans[j][1], spans[j][2]))
                j += 1
            # host spans nest, so closed ones sit above the open ones
            while stack and stack[-1][0] <= mid:
                stack.pop()
            who = stack[-1][1] if stack else "client"
            tally[who] += dur * 1e-9 / len(trace.devices)
    return [[k, v] for k, v in sorted(tally.items(), key=lambda kv: -kv[1])
            ][:top]


#: ops that only hold other ops (a scan's loop): their time is their body's
CONTROL_FLOW = ("while", "conditional", "call")


def top_ops(trace: Trace, top: int = 10) -> List[List]:
    """Device operations that took most time, ``[module/op, seconds]``,
    summed over the window and averaged over devices (control-flow ops,
    which enclose the ops of their bodies, left out)."""
    tally: Dict[str, float] = collections.defaultdict(float)
    for dev in trace.devices:
        ops = dev.ops
        for n, m, s, e in zip(ops.name, ops.module, ops.start, ops.end):
            if n not in CONTROL_FLOW:
                tally[f"{m}/{n}"] += (e - s) * 1e-9 / len(trace.devices)
    return [[k, v] for k, v in sorted(tally.items(), key=lambda kv: -kv[1])
            ][:top]


def modules(trace: Trace, name: str) -> Events:
    """Executions of the program ``name`` (e.g. ``jit__step``), over all
    devices."""
    parts = [d.modules.select([n == name for n in d.modules.name])
             for d in trace.devices]
    return _concat(parts)


def ops(trace: Trace, name: str, module: str = "") -> Events:
    """Device ops of short name ``name``, inside executions of program
    ``module`` where one is given."""
    parts = [d.ops.select([n == name and (not module or m == module)
                           for n, m in zip(d.ops.name, d.ops.module)])
             for d in trace.devices]
    return _concat(parts)


def _concat(parts: List[Events]) -> Events:
    if not parts:
        return Events.empty()
    return Events(sum((p.name for p in parts), []),
                  sum((p.module for p in parts), []),
                  np.concatenate([p.start for p in parts]),
                  np.concatenate([p.end for p in parts]))

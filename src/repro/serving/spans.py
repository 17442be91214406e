"""Host spans and counters of the serving path.

``with span("dp.prefill", req_id=r.req_id) as sp: ...; sp.n = k`` marks
one stretch of host work. A span records exactly while a JAX profiler
session is active (``jax.profiler.TraceAnnotation.is_enabled()``); at
any other time ``span`` returns one shared no-op context, so serving
without the profiler pays for that one check and nothing else.

A recorded span is a ``TraceAnnotation``, so it lands in the profile on
the device trace's clock, and is also kept in :data:`RECORDER` as a
:class:`Span`: times on ``time.perf_counter_ns`` (the clock a client
times requests by), the enclosing span of the same thread, the request
it concerns (inherited from the enclosing span where not given) and its
counter ``n``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: spans kept in memory; older ones are dropped first (about twenty
#: spans an engine step: a minute of serving holds some 30,000)
CAPACITY = 1 << 18


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    req_id: Optional[int]
    n: int


class _NoSpan:
    """The context ``span`` returns while nothing records."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @property
    def n(self) -> int:
        return 0

    @n.setter
    def n(self, value: int) -> None:
        pass


_NO_SPAN = _NoSpan()


class _OpenSpan:
    __slots__ = ("name", "req_id", "n", "_rec", "_parent", "_ann",
                 "_start")

    def __init__(self, rec: "SpanRecorder", name: str,
                 req_id: Optional[int]):
        self.name = name
        self.req_id = req_id
        self.n = 0
        self._rec = rec

    def __enter__(self):
        stack = self._rec._stack()
        self._parent = stack[-1] if stack else None
        if self.req_id is None and self._parent is not None:
            self.req_id = self._parent.req_id
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        # the annotation stamps its own times inside its enter and exit:
        # reading the clock after each keeps the two records together
        self._ann.__exit__(*exc)
        end = time.perf_counter_ns()
        self._rec._stack().pop()
        parent = self._parent
        self._rec._spans.append(Span(
            self.name, self._start, end,
            parent.name if parent is not None else None, self.req_id,
            self.n))
        return False


class SpanRecorder:
    """A bounded in-memory record of the spans of every thread."""

    def __init__(self, capacity: int = CAPACITY):
        self._spans: "collections.deque[Span]" = collections.deque(
            maxlen=capacity)
        self._local = threading.local()

    def _stack(self) -> List[_OpenSpan]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, req_id: Optional[int] = None):
        if not TraceAnnotation.is_enabled():
            return _NO_SPAN
        return _OpenSpan(self, name, req_id)

    def between(self, t0_ns: int, t1_ns: int) -> List[Span]:
        """Finished spans that overlap ``[t0_ns, t1_ns)``, in the order
        they ended."""
        return [s for s in list(self._spans)
                if s.end_ns > t0_ns and s.start_ns < t1_ns]

    def clear(self) -> None:
        self._spans.clear()


#: the process's recorder: the serving path records here
RECORDER = SpanRecorder()
span = RECORDER.span

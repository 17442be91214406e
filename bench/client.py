"""Drives ``FlowServeEngine`` the way users do, and records what they see.

The client keeps its own clock (``time.perf_counter``): each request is
timed from when it was due, and each token when it reaches the client
through ``Request.on_token``. Host spans and counters come from thin
wrappers the client puts around bound methods of the engine, its
scheduler shell and its DP groups; the program itself is not edited.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.traffic.generator import Generator, RequestSpec, schedule

clock = time.perf_counter

#: bound methods wrapped with a host span (and, for some, a counter)
SPANS = (
    ("engine", "step", "FlowServeEngine.step"),
    ("shell", "schedule_prefill_chunks", "TEShell.schedule_prefill_chunks"),
    ("dp", "run_prefill_chunk", "DPGroup.run_prefill_chunk"),
    ("dp", "admit", "DPGroup.admit"),
    ("dp", "decode_launch", "DPGroup.decode_launch"),
    ("dp", "decode_complete", "DPGroup.decode_complete"),
)


@dataclasses.dataclass
class Sent:
    """One request as the client sees it."""
    spec: RequestSpec
    due: float
    sent: float
    req: object
    tokens: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Launch:
    """One decode launch of one DP group: live slots and the sum of
    their context lengths (the positions their new tokens take)."""
    t: float
    n_live: int
    slots: int
    ctx_sum: int
    traced: bool


@dataclasses.dataclass
class Chunk:
    """One prefill chunk as executed: prompt positions ``[start, end)``
    were computed (a prefix-cache hit moves ``start``)."""
    t: float
    start: int
    end: int
    traced: bool


class Recorder:
    """Counters and host spans around the engine's layers."""

    def __init__(self, engine):
        self.traced = False
        self.steps: List[tuple] = []
        self.launches: List[Launch] = []
        self.chunks: List[Chunk] = []
        self.first_chunk_done: set = set()
        #: request id -> (DP group, decode slot) it was admitted to
        self.placed: Dict[int, Tuple[int, int]] = {}
        import jax

        self._annotation = jax.profiler.TraceAnnotation
        owners = {"engine": [engine], "shell": [engine.shell],
                  "dp": list(engine.dps)}
        for kind, attr, name in SPANS:
            for obj in owners[kind]:
                setattr(obj, attr, self._wrap(obj, getattr(obj, attr), name))

    def _wrap(self, obj, fn: Callable, name: str) -> Callable:
        pre = getattr(self, "_pre_" + fn.__name__, None)
        post = getattr(self, "_post_" + fn.__name__, None)
        annotation = self._annotation

        def wrapped(*args, **kw):
            state = pre(obj, *args) if pre is not None else None
            t0 = clock()
            with annotation(name):
                out = fn(*args, **kw)
            if post is not None:
                post(obj, state, t0, out, *args)
            return out
        return wrapped

    # counters ---------------------------------------------------------
    def _post_step(self, engine, state, t0, out):
        self.steps.append((t0, clock()))

    def _pre_decode_launch(self, dp):
        live = [s.position for s in dp.slots if not s.free]
        return len(live), sum(live)

    def _post_decode_launch(self, dp, state, t0, launched):
        if launched:
            self.launches.append(Launch(t0, state[0], dp.max_batch,
                                        state[1], self.traced))

    def _post_admit(self, dp, state, t0, slot, req, *_):
        self.placed[req.req_id] = (dp.dp_id, slot)

    def _post_run_prefill_chunk(self, dp, state, t0, out, work):
        req = work.req
        start = work.start
        if work.is_first:
            start = max(start, req.prefix_hit_tokens)
            self.first_chunk_done.add(req.req_id)
        end = min(work.end, req.prompt_len)
        if end > start:
            self.chunks.append(Chunk(t0, start, end, self.traced))


def busy(engine) -> bool:
    """Whether the engine holds any work (the condition its own
    ``run_until_done`` loops on)."""
    return bool(engine.waiting or engine._ready
                or engine.shell.prefill_sched.pending
                or any(d.active for d in engine.dps))


def collect_finished(engine) -> list:
    out = []
    for d in engine.dps:
        out.extend(d.finished)
        d.finished = []
    return out


class Client:
    """Sends one mix to the engine: open loop on a due-time schedule, or
    closed loop with a fixed number of callers."""

    def __init__(self, engine, mix: dict, seed: int, vocab: int,
                 horizon_s: float):
        from repro.serving.request import Request

        self._Request = Request
        self.engine = engine
        self.mix = mix
        self.sent: List[Sent] = []
        self.closed = mix["loop"] == "closed"
        if self.closed:
            self._gen = Generator(mix, seed, vocab)
            self.clients = int(mix["clients"])
        else:
            self._sched = schedule(mix, seed, vocab, horizon_s)
            self._next = 0
        self.t_start: Optional[float] = None

    def _send(self, spec: RequestSpec, due: float) -> None:
        now = clock()
        rec = Sent(spec, due, now, None)
        req = self._Request(prompt_tokens=list(spec.prompt),
                            max_new_tokens=spec.max_new, ignore_eos=True,
                            temperature=0.0,
                            on_token=lambda _t, r=rec: r.tokens.append(
                                clock()))
        rec.req = req
        self.sent.append(rec)
        self.engine.submit(req)

    def start(self) -> None:
        self.t_start = clock()
        if self.closed:
            for _ in range(self.clients):
                self._send(self._gen.next(), self.t_start)

    def run_until(self, t_end: float,
                  on_time: Optional[Callable[[float], None]] = None) -> None:
        """Send what falls due and step the engine until ``t_end``."""
        eng = self.engine
        while True:
            now = clock()
            if on_time is not None:
                on_time(now)
            if now >= t_end:
                return
            if not self.closed:
                while (self._next < len(self._sched)
                       and self.t_start + self._sched[self._next][0] <= now):
                    due, spec = self._sched[self._next]
                    self._send(spec, self.t_start + due)
                    self._next += 1
            if busy(eng):
                eng.step()
                done = collect_finished(eng)
                if self.closed:
                    for _ in done:
                        self._send(self._gen.next(), clock())
            else:
                nxt = (self.t_start + self._sched[self._next][0]
                       if not self.closed and self._next < len(self._sched)
                       else t_end)
                time.sleep(max(min(nxt, t_end) - clock(), 0.0))


# ---------------------------------------------------------------------------
# what users see, over the window [t0, t1)
# ---------------------------------------------------------------------------
def percentile(vals, q: float) -> float:
    """Linear-interpolated percentile (the arithmetic of the simulator's
    ``sim/metrics.py``, copied)."""
    return float(np.percentile(np.asarray(vals, np.float64), q))


def ttft_sample(sent: List[Sent], t0: float, t1: float) -> List[float]:
    """Seconds from due time to first token, for every request due in
    the window; one with no first token by ``t1`` enters at its wait so
    far, so a stall cannot hide."""
    out = []
    for s in sent:
        if t0 <= s.due < t1:
            first = s.tokens[0] if s.tokens else None
            out.append((first if first is not None and first < t1 else t1)
                       - s.due)
    return out


def itl_sample(sent: List[Sent], t0: float, t1: float) -> List[float]:
    """Gaps between consecutive tokens of every request whose later
    token reached the client in the window, plus, for each request still
    decoding at ``t1``, the open gap since its last token."""
    out = []
    for s in sent:
        toks = [t for t in s.tokens if t < t1]
        for a, b in zip(toks, toks[1:]):
            if b >= t0:
                out.append(b - a)
        if toks and len(toks) < s.spec.max_new:
            out.append(t1 - toks[-1])
    return out


def tpot_sample(sent: List[Sent], t0: float, t1: float) -> List[float]:
    """Each request's mean gap between its tokens (time per output
    token), for every request due in the window that has its first
    token by ``t1``; one still decoding at ``t1`` counts the open gap
    since its last token as one more gap, so a stall cannot hide."""
    out = []
    for s in sent:
        if not t0 <= s.due < t1:
            continue
        toks = [t for t in s.tokens if t < t1]
        if len(toks) == s.spec.max_new and len(toks) > 1:
            out.append((toks[-1] - toks[0]) / (len(toks) - 1))
        elif toks and len(toks) < s.spec.max_new:
            out.append((t1 - toks[0]) / len(toks))
    return out


def tokens_in(sent: List[Sent], t0: float, t1: float) -> int:
    return sum(1 for s in sent for t in s.tokens if t0 <= t < t1)

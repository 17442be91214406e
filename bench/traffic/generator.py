"""The benchmark's one traffic generator. A mix is a JSON file beside
this one (``<mix>.json``) holding only parameters; a new mix is a new
file, found by its name.

Keys of a mix:

* ``loop``: ``"open"`` (requests due on an arrival schedule, whatever
  the server does) or ``"closed"`` (``clients`` callers, each sending
  its next request when its last one finishes).
* ``arrival`` (open loop): ``{"kind": "poisson", "rate": r}`` or
  ``{"kind": "gamma", "rate": r, "cv": c}`` for bursts; rate in
  requests per second.
* ``prompt`` / ``output``: a length distribution, ``{"dist":
  "lognormal", "median", "sigma", "min", "max"}``, ``{"dist":
  "uniform", "min", "max"}`` or ``{"dist": "fixed", "value"}``.
* ``system_prompt``: tokens of one shared system prompt at the head of
  every prompt (0: none). With it, ``prompt`` is the first user turn.
* ``sessions``: ``{"share", "extend", "max_turns", "max_prompt"}``. A
  share ``share`` of arrivals continues a live session: its prompt is
  that session's last prompt plus ``extend`` new tokens, so the old
  prompt is a prefix of the new one (the arithmetic of the simulator's
  ``sim/workload.py``, copied). Live sessions take their turns in
  order, the one waiting longest first. A session retires after
  ``max_turns`` turns or once its prompt reaches ``max_prompt``, which
  also caps every prompt.
* ``sources``: where each value comes from (read by people, not here).
* ``warm_seconds``: the unrecorded stretch of the same traffic that
  runs before the measured window.

Every seed gets the same sizes and arrival gaps in another order: each
block of ``BLOCK`` draws takes the ``BLOCK`` stratified quantiles of
its distribution in an order drawn from the seed, and each block of
gaps is scaled to last exactly ``BLOCK / rate``. Each block of
``BLOCK`` arrivals holds the same number of session turns; first turns
draw their lengths from one stratified stream and later turns from
another, and a turn always continues the session that has waited
longest. So runs with different seeds do the same work, and the seed
moves only the order, the token ids and the weights.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import Deque, Iterator, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
BLOCK = 32
_NORMAL = statistics.NormalDist()


def load_mix(name: str) -> dict:
    """The mix ``name`` from ``<name>.json`` beside this file."""
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def quantile(spec: dict, u: float) -> int:
    """The length at quantile ``u`` of distribution ``spec``."""
    dist = spec["dist"]
    if dist == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["min"]), int(spec["max"])
    if dist == "uniform":
        return min(lo + int(u * (hi - lo + 1)), hi)
    if dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
        return int(min(max(round(x), lo), hi))
    raise ValueError(f"unknown length distribution {dist!r}")


def _gap_quantiles(arrival: dict, u: np.ndarray) -> np.ndarray:
    """Inter-arrival gaps at quantiles ``u`` for a mean gap of 1."""
    kind = arrival["kind"]
    if kind == "poisson":
        return -np.log1p(-u)
    if kind == "gamma":
        # quantiles of one fixed large sample: the same for every seed
        shape = 1.0 / arrival["cv"] ** 2
        table = np.sort(np.random.default_rng(0).gamma(shape, 1.0 / shape,
                                                       1 << 16))
        return table[np.minimum((u * len(table)).astype(int),
                                len(table) - 1)]
    raise ValueError(f"unknown arrival kind {kind!r}")


class Stratified:
    """Quantile levels in [0, 1): each block of ``BLOCK`` holds the
    ``BLOCK`` midpoints ``(i + 0.5) / BLOCK`` in an order drawn from
    ``rng``."""

    def __init__(self, rng: np.random.Generator):
        self.rng = rng
        self._buf: List[float] = []

    def next(self) -> float:
        if not self._buf:
            self._buf = list((self.rng.permutation(BLOCK) + 0.5) / BLOCK)
        return self._buf.pop()


@dataclasses.dataclass
class RequestSpec:
    prompt: List[int]
    max_new: int


class Generator:
    """Yields the requests of one mix for one seed, in order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        root = np.random.SeedSequence(seed)
        # the second stream is spare: the others keep their draws
        tok, _, u_prompt, u_out, u_ext, cont = (
            np.random.default_rng(s) for s in root.spawn(6))
        self._tok = tok
        self._u_prompt = Stratified(u_prompt)
        self._u_out = Stratified(u_out)
        self._u_ext = Stratified(u_ext)
        self._cont = cont
        self._cont_buf: List[bool] = []
        self.system = self._tokens(int(mix.get("system_prompt", 0)))
        self.sessions = mix.get("sessions")
        self._cap = int(self.sessions["max_prompt"]) if self.sessions \
            else None
        #: live sessions, the one waiting longest first
        self._live: Deque[Tuple[List[int], int]] = collections.deque()

    def _tokens(self, n: int) -> List[int]:
        return self._tok.integers(2, self.vocab, n).tolist()

    def _continues(self) -> bool:
        """Exactly ``round(share * BLOCK)`` continuations per block."""
        if not self._cont_buf:
            k = round(self.sessions["share"] * BLOCK)
            flags = np.zeros(BLOCK, bool)
            flags[:k] = True
            self._cont_buf = list(self._cont.permutation(flags))
        return bool(self._cont_buf.pop())

    def next(self) -> RequestSpec:
        mix = self.mix
        out = quantile(mix["output"], self._u_out.next())
        if self.sessions is not None and self._continues() and self._live:
            prev, turns = self._live.popleft()
            ext = quantile(self.sessions["extend"], self._u_ext.next())
            toks = (prev + self._tokens(ext))[:self._cap]
            if (turns + 1 < self.sessions["max_turns"]
                    and len(toks) < self._cap):
                self._live.append((toks, turns + 1))
            return RequestSpec(toks, out)
        toks = (self.system
                + self._tokens(quantile(mix["prompt"],
                                        self._u_prompt.next())))
        if self._cap is not None:
            toks = toks[:self._cap]
            if len(toks) < self._cap and self.sessions["max_turns"] > 1:
                self._live.append((toks, 1))
        return RequestSpec(toks, out)

    def __iter__(self) -> Iterator[RequestSpec]:
        while True:
            yield self.next()


def arrival_times(mix: dict, seed: int, seconds: float) -> List[float]:
    """Due times in ``[0, seconds)`` of an open-loop mix: gaps of mean
    ``1 / rate``, stratified per block and each block scaled to last
    exactly ``BLOCK / rate``."""
    arrival = mix["arrival"]
    rate = float(arrival["rate"])
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(7)[6])
    times: List[float] = []
    t = 0.0
    levels = (np.arange(BLOCK) + 0.5) / BLOCK
    block = _gap_quantiles(arrival, levels)
    block *= BLOCK / rate / block.sum()
    while True:
        for g in block[rng.permutation(BLOCK)]:
            t += float(g)
            if t >= seconds:
                return times
            times.append(t)


def schedule(mix: dict, seed: int, vocab: int, seconds: float
             ) -> List[Tuple[float, RequestSpec]]:
    """The open-loop schedule: ``(due time, request)`` pairs."""
    gen = Generator(mix, seed, vocab)
    return [(t, gen.next()) for t in arrival_times(mix, seed, seconds)]


def reachable_lengths(mix: dict, n: int = 4096
                      ) -> Tuple[List[int], Optional[int]]:
    """Every prompt length a mix can send (read off a long stretch of
    its stratified draws) and the shared head every prompt starts with
    (``None`` without one). Warm-up compiles the shapes these reach."""
    gen = Generator(mix, 0, 1 << 20)
    lens = sorted({len(gen.next().prompt) for _ in range(n)})
    shared = int(mix.get("system_prompt", 0)) or None
    return lens, shared

"""Faults planted under the timed path, to show that ``correct`` comes
out false when the served tokens go wrong: each takes the place of
``JAXBackend.decode_sample``, the decode-and-sample program every decode
step of every DP group calls. Used by ``tests/test_bench_faults.py`` at
a small size and by ``calibrate.py --fault`` at a cell's own size; the
benchmark's own runs never plant one."""
from __future__ import annotations

import numpy as np

from repro.serving.backend import JAXBackend

_decode = JAXBackend.decode_sample


def state_unchanged(self, cache, tokens, positions, temps, step, **kw):
    """The step returns the cache it was given: no token is written."""
    toks, _ = _decode(self, cache, tokens, positions, temps, step,
                      donate=False)
    return toks, cache


def half_batch(self, cache, tokens, positions, temps, step, **kw):
    """Every second live slot (free slots sit at position 0) is left
    out of the step."""
    tokens, positions = np.array(tokens), np.array(positions)
    out = np.flatnonzero(positions > 0)[::2]
    tokens[out] = 0
    positions[out] = 0
    return _decode(self, cache, tokens, positions, temps, step, **kw)


def token_altered(self, cache, tokens, positions, temps, step, **kw):
    """Every fifth step's tokens are altered where they are produced."""
    toks, new = _decode(self, cache, tokens, positions, temps, step, **kw)
    if step % 5 == 4:
        toks = (toks + 1) % self.vocab_size
    return toks, new


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}


def plant(name: str) -> None:
    """Puts fault ``name`` in the program's place, for this process."""
    JAXBackend.decode_sample = FAULTS[name]

"""Proactive jitter control (§4.4): manual GC, core pinning, step cache.

The paper's three mitigations map as:
  * Core pinning            → os.sched_setaffinity (best-effort).
  * PTA graph caching       → jax.jit's compilation cache (we additionally
                              pre-warm the decode step so the first global
                              dispatch doesn't hit compile jitter).
  * Manual Python GC        → disable automatic collection, collect every
                              N forward passes at a controlled point.
"""
from __future__ import annotations

import contextlib
import gc
import os
import time
from typing import Callable, List, Optional

from repro.serving.spans import span


class ProactiveGC:
    def __init__(self, every_n_steps: int = 200, enabled: bool = True):
        self.every = every_n_steps
        self.enabled = enabled
        self.steps = 0
        self.collections = 0
        if enabled:
            gc.disable()

    def step(self) -> Optional[float]:
        """Call once per forward pass; collects at controlled intervals.
        Returns GC duration when a collection ran."""
        if not self.enabled:
            return None
        self.steps += 1
        if self.steps % self.every:
            return None
        t0 = time.monotonic()
        with span("dp.gc"):
            gc.collect()
        dt = time.monotonic() - t0
        self.collections += 1
        return dt

    def close(self) -> None:
        if self.enabled:
            gc.enable()


def pin_to_core(core: Optional[int] = None) -> bool:
    """Pin this executor process/thread to one CPU core (best-effort)."""
    if core is None or not hasattr(os, "sched_setaffinity"):
        return False
    try:
        os.sched_setaffinity(0, {core})
        return True
    except (OSError, ValueError):
        return False


def prewarm(fns_and_args: List) -> float:
    """Compile-cache warmup (PTA-caching analogue): run each (fn, args)
    once before serving so graph launches are cache hits."""
    t0 = time.monotonic()
    for fn, args in fns_and_args:
        out = fn(*args)
        for leaf in _leaves(out):
            getattr(leaf, "block_until_ready", lambda: None)()
    return time.monotonic() - t0


def _leaves(x):
    import jax
    return jax.tree.leaves(x)


@contextlib.contextmanager
def jitter_guard(gc_ctl: ProactiveGC):
    """Wrap a dispatch-critical section: no GC inside."""
    was = gc.isenabled()
    if was:
        gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()

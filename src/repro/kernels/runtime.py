"""Backend detection shared by the kernel wrappers.

Every ``kernels/*/ops.py`` wrapper takes ``interpret: Optional[bool]``;
``None`` resolves via :func:`default_interpret` so the same call site
runs the Pallas interpreter on CPU (tests, sims) and compiles the real
kernel on TPU — no per-deployment plumbing of the flag.
"""
from __future__ import annotations

import jax


def on_cpu() -> bool:
    """True when the active JAX backend is the CPU driver."""
    return jax.default_backend() == "cpu"


def default_interpret() -> bool:
    """Interpret Pallas kernels only where they cannot compile (CPU)."""
    return on_cpu()


def resolve_interpret(interpret):
    """``None`` → :func:`default_interpret`; anything else passes through
    to ``pallas_call`` (a bool, or ``pltpu.InterpretParams`` for the
    TPU-semantics interpreter)."""
    return default_interpret() if interpret is None else interpret

"""Operations and bytes the work requires, from its shapes, and the
least time the chip could take for them. One module per kernel or
program; each counts what the step needs, not what an implementation
happens to do (padded rows, whole cache buffers, copies)."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device missing from the table is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        known = sorted(k for k in table if k != "source")
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS.name} (known: {known})")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: compute- or bandwidth-bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def experts_reached(n_tokens: float, n_experts: int, top_k: int) -> float:
    """Expected number of distinct experts that ``n_tokens`` tokens reach
    with ``top_k`` distinct experts each, routing uniform: the weights a
    step must read at least once. Routing measured by the program would
    replace this model (it is not observable from outside)."""
    if n_tokens <= 0:
        return 0.0
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** n_tokens)

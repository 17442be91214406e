"""Production meshes for the CloudMatrix384-scale dry-run.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets ``xla_force_host_platform_device_count`` before
any jax initialization; tests and benches keep the default single device).
"""
from __future__ import annotations

from repro.models.mesh_ctx import make_auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2×16×16 = 512 chips for two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Best-effort mesh over the locally available devices (serving/tests)."""
    model_parallel = max(1, min(model_parallel, n_devices))
    data = n_devices // model_parallel
    return make_auto_mesh((data, model_parallel), ("data", "model"))

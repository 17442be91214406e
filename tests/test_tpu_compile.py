"""Compile the served path's kernels and decode steps for a described
TPU v5e — no chip needed, nothing runs.

Interpret mode cannot catch what only the TPU compiler refuses (tile
alignment, scoped-VMEM budgets, layouts); these compiles can. Shapes are
the ones ``chip_smoke.py`` serves: deepseek-moe-16b expert buckets at a
decode step (2 slots per DP group) and at prefill chunks, gmm at
DeepSeek-V3 widths (8 of 256 experts: an EP-32 share; all 256 do not
fit one chip), and whole decode steps at published widths.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every xdist worker
imports this file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.kernels.gmm.ops import expert_ffn
from repro.kernels.route_pack.ops import fused_route_pack

pytestmark = pytest.mark.slow  # compile-heavy: see tests/README.md

BF16 = jnp.bfloat16
DS_MOE = get_config("deepseek-moe-16b")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def mesh1(topo):
    from repro.models.mesh_ctx import make_auto_mesh

    return make_auto_mesh((1, 1), ("data", "model"), topo.devices[:1])


def _shape(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def _compile(fn, *args):
    """Compile for the described chip; returns the compiled HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _capacity(T, moe=DS_MOE.moe):
    """Per-expert capacity of the one-chip gather path (models/ffn.py)."""
    return max(int(T * moe.top_k / moe.num_experts * moe.capacity_factor),
               4)


# decode step (2 slots), prefill chunks, and the largest chunk the
# scheduler emits at its default 8,192-token budget
MOE_TOKENS = [2, 1024, 8192]


@pytest.mark.parametrize("T", MOE_TOKENS)
@pytest.mark.parametrize("placement", [False, True])
def test_gmm_deepseek_moe_16b(mesh1, T, placement):
    e, d = DS_MOE.moe, DS_MOE.d_model
    E, C, f = e.num_experts, _capacity(T), e.expert_d_ff
    args = [_shape(mesh1, (E, C, d), BF16),
            _shape(mesh1, (E, d, f), BF16), _shape(mesh1, (E, d, f), BF16),
            _shape(mesh1, (E, f, d), BF16)]
    if placement:        # one redundant slot per expert (EPLB budget)
        fn = lambda b, g, u, dn, o: expert_ffn(  # noqa: E731
            b, g, u, dn, phys_owner=o, use_pallas=True, interpret=False)
        args[0] = _shape(mesh1, (2 * E, C, d), BF16)
        args.append(_shape(mesh1, (2 * E,), jnp.int32))
    else:
        fn = functools.partial(expert_ffn, use_pallas=True,
                               interpret=False)
    assert "tpu_custom_call" in _compile(fn, *args)


@pytest.mark.parametrize("C", [8, 128])
def test_gmm_deepseek_v3_widths(mesh1, C):
    d, f, E = 7168, 2048, 8
    fn = functools.partial(expert_ffn, use_pallas=True, interpret=False)
    hlo = _compile(fn, _shape(mesh1, (E, C, d), BF16),
                   _shape(mesh1, (E, d, f), BF16),
                   _shape(mesh1, (E, d, f), BF16),
                   _shape(mesh1, (E, f, d), BF16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("T", MOE_TOKENS)
def test_route_pack_deepseek_moe_16b(mesh1, T):
    e, d = DS_MOE.moe, DS_MOE.d_model
    N = T * e.top_k
    fn = functools.partial(fused_route_pack, k=e.top_k,
                           n_dest=e.num_experts, capacity=_capacity(T),
                           use_pallas=True, interpret=False)
    hlo = _compile(fn, _shape(mesh1, (T, d), BF16),
                   _shape(mesh1, (N,), jnp.int32),
                   _shape(mesh1, (N,), jnp.bool_))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("arch,layers,batch", [
    ("internlm2-1.8b", None, 4),
    ("deepseek-moe-16b", 4, 2),
])
def test_decode_sample_published_widths(mesh1, monkeypatch, arch, layers,
                                        batch):
    """The served greedy decode step (``JAXBackend.decode_sample``) at
    published widths, from shapes only. The kernel wrappers pick Pallas
    only off the CPU, so the test points them at the chip."""
    import repro.kernels.gmm.ops as gmm_ops
    import repro.kernels.route_pack.ops as rp_ops
    from repro.models.mesh_ctx import MeshCtx
    from repro.models.transformer import build_model
    from repro.serving.backend import JAXBackend

    for mod in (gmm_ops, rp_ops):
        monkeypatch.setattr(mod, "on_cpu", lambda: False)
        monkeypatch.setattr(mod, "resolve_interpret", lambda _: False)
    cfg = get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    model = build_model(cfg, MeshCtx(mesh=mesh1, batch_axes=("data",),
                                     remat="none"))
    as_chip = functools.partial(jax.tree.map,
                                lambda s: _shape(mesh1, s.shape, s.dtype))
    params = as_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    max_len = 1152
    be = JAXBackend(model, params, max_len=max_len)
    B = batch
    compiled = be._decode_sample.lower(
        params, as_chip(model.cache_spec(B, max_len)),
        _shape(mesh1, (B, 1), jnp.int32), _shape(mesh1, (B,), jnp.int32),
        _shape(mesh1, (B,), jnp.float32), _shape(mesh1, (2,), jnp.uint32),
        _shape(mesh1, (), jnp.int32), None, stochastic=False).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 15 * 2**30, used          # one 16 GB v5e chip
    assert ("tpu_custom_call" in compiled.as_text()) == cfg.has_moe

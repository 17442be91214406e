"""The served device programs name their parts: the op metadata of the
decode, prefill-chunk and write_slot programs carries the scopes a
profile or a compiled program shows for each op."""
import re

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models.mesh_ctx import make_smoke_ctx
from repro.models.transformer import build_model
from repro.serving.backend import JAXBackend


def test_served_programs_carry_named_scopes():
    model = build_model(get_config("deepseek-moe-16b-smoke"),
                        make_smoke_ctx())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    be = JAXBackend(model, params, max_len=64)
    cache = jax.eval_shape(lambda: model.init_cache(2, 64))
    one = jax.eval_shape(lambda: model.init_cache(1, 64))
    i32 = jnp.int32

    def scopes(lowered):
        """Every path segment of the program's op locations."""
        names = re.findall(r'loc\("([^"]*)"',
                           lowered.as_text(debug_info=True))
        return {seg for n in names for seg in n.split("/")}

    decode = scopes(be._decode_sample.lower(
        params, cache, jnp.zeros((2, 1), i32), jnp.zeros((2,), i32),
        jnp.zeros((2,), jnp.float32), be._base_key, i32(0), None,
        stochastic=False))
    for scope in ("attention", "mlp", "moe_routing", "moe_dispatch",
                  "moe_experts", "moe_combine", "moe_shared",
                  "output_head", "sampling"):
        assert scope in decode, scope
    chunk = scopes(be._prefill_chunk.lower(
        params, one, jnp.zeros((1, 16), i32), i32(0),
        jnp.asarray([15], i32)))
    for scope in ("attention", "moe_experts", "output_head"):
        assert scope in chunk, scope
    assert "write_slot" in scopes(be._write_slot.lower(cache, one, i32(1)))

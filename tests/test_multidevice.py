"""Multi-device XCCL semantics, tested in subprocesses with 8 host
devices (the main pytest process keeps 1 device per the dry-run
isolation rule)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # compile-heavy: see tests/README.md

_ENV = dict(os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH="src")


def run_prog(body: str) -> str:
    prog = textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", prog], env=_ENV,
                         capture_output=True, text=True, cwd=".",
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_dispatch_combine_and_a2e_8dev():
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        import functools
        from jax.sharding import PartitionSpec as P
        from repro.models.mesh_ctx import make_auto_mesh
        from repro.xccl.routing import (dispatch_local, combine_local,
                                        make_a2e_e2a)
        assert jax.device_count() == 8, jax.device_count()
        mesh = make_auto_mesh((8,), ("ep",))

        # ---- dispatch/combine round trip (§3.2) -------------------------
        E, d, n_loc = 16, 32, 24
        def body(x, idx):
            buckets, state = dispatch_local(
                x[0], idx[0], ep_axis="ep", ep_size=8, n_experts=E,
                capacity_factor=8.0, quantize=False)
            # identity "expert": combine must reconstruct the send payload
            y = combine_local(buckets, state, ep_axis="ep", ep_size=8,
                              quantize=False)
            return y[None]
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((8, n_loc, d)), jnp.float32)
        idx = jnp.asarray(rng.integers(0, E, (8, n_loc)), jnp.int32)
        f = jax.shard_map(body, mesh=mesh,
                          in_specs=(P("ep", None, None), P("ep", None)),
                          out_specs=P("ep", None, None), check_vma=False)
        y = f(x, idx)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=1e-5, atol=1e-5)
        print("dispatch/combine OK")

        # ---- quantized wire: error bounded ------------------------------
        def body_q(x, idx):
            buckets, state = dispatch_local(
                x[0], idx[0], ep_axis="ep", ep_size=8, n_experts=E,
                capacity_factor=8.0, quantize=True)
            y = combine_local(buckets, state, ep_axis="ep", ep_size=8,
                              quantize=True)
            return y[None]
        fq = jax.shard_map(body_q, mesh=mesh,
                           in_specs=(P("ep", None, None), P("ep", None)),
                           out_specs=P("ep", None, None), check_vma=False)
        yq = fq(x, idx)
        err = float(jnp.max(jnp.abs(yq - x)))
        assert err < 0.05, err
        print("quantized dispatch OK", err)

        # ---- A2E/E2A trampoline (§3.3): 4 attention + 8 expert ranks ----
        n_attn, n_exp = 4, 8
        a2e, e2a = make_a2e_e2a(mesh, "ep", n_attn, n_exp)
        C = 4
        payload = jnp.zeros((8, 1, n_exp, C, d))
        rank_ids = jnp.arange(8, dtype=jnp.float32)
        # attention rank a sends value (a+1) to every expert bucket
        payload = payload.at[:n_attn].set(
            (rank_ids[:n_attn] + 1)[:, None, None, None, None])
        payload = payload.reshape(8, n_exp, C, d)
        staged = a2e(payload)
        # every expert rank must now hold one bucket from each attention
        # rank (via its trampoline), i.e. values {1..4} present
        got = np.asarray(staged).reshape(8, n_exp, C, d)
        for r in range(8):
            vals = set(np.unique(got[r, :n_attn, 0, 0]).tolist())
            assert vals == {1.0, 2.0, 3.0, 4.0}, (r, vals)
        back = e2a(staged)
        # E2A must return the payload to the attention ranks
        orig = np.asarray(payload).reshape(8, n_exp, C, d)
        np.testing.assert_allclose(np.asarray(back)[:n_attn].sum(),
                                   orig[:n_attn].sum())
        print("a2e/e2a OK")
    """)
    assert "dispatch/combine OK" in out
    assert "a2e/e2a OK" in out


def test_sharded_model_step_8dev():
    """A smoke model's train + decode step on a 4×2 mesh must match the
    1-device result (the distribution layer is numerics-preserving)."""
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.mesh_ctx import (MeshCtx, make_auto_mesh,
                                           make_smoke_ctx)
        from repro.models.transformer import build_model
        assert jax.device_count() == 8
        cfg = get_config("deepseek-moe-16b-smoke")
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        ctx = MeshCtx(mesh=mesh, batch_axes=("data",), remat="none")
        m = build_model(cfg, ctx)
        params = m.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        loss, _ = m.forward_train(params, toks, toks)
        # single-device reference
        ctx1 = make_smoke_ctx()
        m1 = build_model(cfg, ctx1)
        loss1, _ = m1.forward_train(params, toks, toks)
        rel = abs(float(loss) - float(loss1)) / max(abs(float(loss1)), 1e-6)
        assert rel < 0.02, (float(loss), float(loss1))
        print("sharded train OK", float(loss), float(loss1))

        logits, cache = m.prefill(params, toks[:, :24])
        logits1, _ = m1.prefill(params, toks[:, :24])
        a, b = np.asarray(logits), np.asarray(logits1)
        rel = float(np.max(np.abs(a - b))) / float(np.max(np.abs(b)))
        assert rel < 0.05, rel
        print("sharded prefill OK", rel)
    """)
    assert "sharded train OK" in out
    assert "sharded prefill OK" in out


def test_sharded_ep_placement_decode_8dev():
    """EPLB placement on a sharded-EP decode mesh (the lifted §4.5
    restriction): budget-0 placement must be bit-identical to logical
    sharded routing, and a replica-carrying table must produce the same
    MoE output as the single-device replicated-placement path while the
    slot plane block-shards over 4 EP ranks."""
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.models.ffn import moe_apply, moe_init
        from repro.models.mesh_ctx import (MeshCtx, make_auto_mesh,
                                           make_smoke_ctx)
        from repro.serving.eplb import (build_expert_map,
                                        build_placement_table,
                                        identity_placement)
        assert jax.device_count() == 8
        # capacity_factor 8 → no bucket overflows, so the replicated and
        # sharded paths see identical token sets (drops are per-bucket)
        cfg = ModelConfig(name="tiny-moe", d_model=16, d_ff=32,
                          num_layers=2, num_heads=2, vocab_size=64,
                          moe=MoEConfig(num_experts=8, top_k=2,
                                        expert_d_ff=16,
                                        capacity_factor=8.0))
        E = cfg.moe.num_experts
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        ctx = MeshCtx(mesh=mesh, batch_axes=("data",), remat="none")
        params = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 4, cfg.d_model),
                              jnp.float32)

        # ---- budget 0: sharded placement ≡ logical sharded routing ----
        y0, aux0 = moe_apply(params, x, cfg=cfg, ctx=ctx, mode="decode")
        t0 = identity_placement(1, E)
        y1, aux1 = moe_apply(params, x, cfg=cfg, ctx=ctx, mode="decode",
                             placement=t0.layer(0))
        assert bool(jnp.all(y0 == y1)), "budget-0 must be bit-identical"
        np.testing.assert_array_equal(
            np.asarray(aux0["expert_counts"]),
            np.asarray(aux1["expert_counts"]))
        print("sharded budget0 OK")

        # ---- replicas: sharded-EP output == replicated-placement ------
        rng = np.random.default_rng(0)
        counts = rng.integers(0, 20, (E, 4))
        counts[1] += 300
        em = build_expert_map(counts, E, 3, n_npus=4)
        t = build_placement_table([em], E)        # n_phys=11: pads to 12
        assert int(np.max(np.asarray(t.n_replicas))) > 1
        ys, _ = moe_apply(params, x, cfg=cfg, ctx=ctx, mode="decode",
                          placement=t.layer(0))
        yr, _ = moe_apply(params, x, cfg=cfg, ctx=make_smoke_ctx(),
                          mode="decode", placement=t.layer(0))
        np.testing.assert_allclose(np.asarray(ys), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        # and it still matches the plain decode step (replica slots
        # compute with their owner's weights)
        np.testing.assert_allclose(np.asarray(ys), np.asarray(y0),
                                   rtol=1e-5, atol=1e-5)
        print("sharded placement OK")
    """)
    assert "sharded budget0 OK" in out
    assert "sharded placement OK" in out


def test_distributed_decode_attention_8dev():
    """Flash-decoding over a seq-sharded cache must match the local ref."""
    out = run_prog("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.mesh_ctx import MeshCtx, make_auto_mesh
        from repro.models.attention import decode_attention_distributed
        from repro.models.cache_ref import CacheRef
        from repro.kernels.decode_attention.ref import decode_attention_ref
        assert jax.device_count() == 8
        mesh = make_auto_mesh((1, 8), ("data", "model"))
        ctx = MeshCtx(mesh=mesh, batch_axes=("data",), remat="none")
        rng = np.random.default_rng(0)
        B, H, KV, hd, L = 2, 8, 4, 32, 64
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.float32)
        kn = jnp.asarray(rng.standard_normal((B, 1, KV, hd)), jnp.float32)
        vn = jnp.asarray(rng.standard_normal((B, 1, KV, hd)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((1, B, L, KV, hd)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((1, B, L, KV, hd)), jnp.float32)
        pos = jnp.asarray([40, 41], jnp.int32)
        ref = CacheRef({"k": ck, "v": cv}, 0)
        out, nref = decode_attention_distributed(q, kn, vn, ref, pos, ctx)
        # reference with the new token scattered in
        k2 = np.asarray(ck[0]).copy(); v2 = np.asarray(cv[0]).copy()
        for b in range(B):
            k2[b, int(pos[b])] = np.asarray(kn[b, 0])
            v2[b, int(pos[b])] = np.asarray(vn[b, 0])
        want = decode_attention_ref(q[:, 0], jnp.asarray(k2),
                                    jnp.asarray(v2), pos)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        print("distributed decode attention OK")
    """)
    assert "distributed decode attention OK" in out


def test_sharded_ep_capacity_not_crowded_8dev():
    """Under sharded EP, assignments a rank does not own (decode gather)
    and empty receive rows (prefill all-to-all) must take no expert
    capacity. With a capacity that holds every token, the sharded MoE
    must equal a dropless one-device reference in both modes."""
    out = run_prog("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.models.ffn import moe_apply, moe_init
        from repro.models.mesh_ctx import (MeshCtx, make_auto_mesh,
                                           make_smoke_ctx)
        assert jax.device_count() == 8
        # 64 experts, top-6: per-expert capacity N/E*cf*4 >= T from
        # cf >= E/(4k); cf 3 keeps every real assignment, while the ~3/4
        # of rows other ranks own would overflow a local expert
        cfg = ModelConfig(name="tiny-moe", d_model=16, d_ff=32,
                          num_layers=2, num_heads=2, vocab_size=64,
                          moe=MoEConfig(num_experts=64, top_k=6,
                                        expert_d_ff=16,
                                        capacity_factor=3.0))
        ref_cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=11.0))
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        ctx = MeshCtx(mesh=mesh, batch_axes=("data",), remat="none")
        params = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
        for mode, shape in (("decode", (8, 1)), ("prefill", (2, 16))):
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  shape + (cfg.d_model,), jnp.float32)
            ys, _ = moe_apply(params, x, cfg=cfg, ctx=ctx, mode=mode)
            yr, _ = moe_apply(params, x, cfg=ref_cfg, ctx=make_smoke_ctx(),
                              mode=mode)
            np.testing.assert_allclose(np.asarray(ys), np.asarray(yr),
                                       rtol=1e-5, atol=1e-5)
            print("uncrowded", mode, "OK")
    """)
    assert "uncrowded decode OK" in out
    assert "uncrowded prefill OK" in out

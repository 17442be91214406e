"""Per-kernel shape/dtype sweeps: interpret-mode Pallas vs pure-jnp oracle
(assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# int8_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(64, 256, 128), (100, 300, 50),
                                   (8, 128, 128), (256, 1024, 512),
                                   (1, 64, 17)])
def test_int8_matmul(m, k, n):
    from repro.kernels.int8_matmul.ops import quantized_matmul
    from repro.kernels.int8_matmul.ref import int8_matmul_ref
    xq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    xs = jnp.asarray(rng.random(m) + 0.1, jnp.float32)
    ws = jnp.asarray(rng.random(n) + 0.1, jnp.float32)
    got = quantized_matmul(xq, xs, wq, ws)
    ref = int8_matmul_ref(xq, xs, wq, ws)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5)


# ---------------------------------------------------------------------------
# gmm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("E,C,d,f", [(4, 16, 64, 128), (8, 64, 128, 256),
                                     (2, 100, 32, 96), (1, 8, 16, 48)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm(E, C, d, f, dtype):
    from repro.kernels.gmm.ops import expert_ffn
    from repro.kernels.gmm.ref import gmm_ref
    b = jnp.asarray(rng.standard_normal((E, C, d)) * 0.3, dtype)
    wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, dtype)
    wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, dtype)
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, dtype)
    got = expert_ffn(b, wg, wu, wd, use_pallas=True, interpret=True)
    ref = gmm_ref(b, wg, wu, wd)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("E,S,C,d,f", [
    (4, 6, 16, 64, 128), (8, 11, 24, 32, 96), (2, 2, 8, 16, 48),
    (1, 3, 100, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_placement_gmm_bit_identical_to_gathered(E, S, C, d, f, dtype):
    """Owner-indexed GMM (scalar-prefetch weight streaming) must be
    BIT-identical to the same kernel on owner-gathered weights — the
    gather is the only thing it removes."""
    from repro.kernels.gmm.ops import expert_ffn
    from repro.kernels.gmm.ref import placement_gmm_ref
    b = jnp.asarray(rng.standard_normal((S, C, d)) * 0.3, dtype)
    wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, dtype)
    wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, dtype)
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, dtype)
    owner = jnp.asarray(rng.integers(0, E, S), jnp.int32)
    free = expert_ffn(b, wg, wu, wd, phys_owner=owner,
                      use_pallas=True, interpret=True)
    gathered = expert_ffn(b, wg[owner], wu[owner], wd[owner],
                          use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(free), np.asarray(gathered))
    # and the oracle agrees within kernel tolerance
    ref = placement_gmm_ref(b, wg, wu, wd, owner)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(free), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_placement_gmm_budget0_identity():
    """phys_owner = arange (budget-0 table) must reproduce the plain
    grouped matmul bit-for-bit on both execution paths."""
    from repro.kernels.gmm.ops import expert_ffn
    E, C, d, f = 4, 16, 32, 64
    b = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    wg = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32)
    ident = jnp.arange(E, dtype=jnp.int32)
    for up in (True, False):
        a = expert_ffn(b, wg, wu, wd, phys_owner=ident, use_pallas=up,
                       interpret=True)
        p = expert_ffn(b, wg, wu, wd, use_pallas=up, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(p))


def test_expert_ffn_wiring_bit_identical_to_legacy_einsum():
    """models/ffn._expert_ffn now routes through kernels/gmm.ops; on the
    CPU fallback (use_pallas=False ⇒ gmm_ref) the result must equal the
    pre-wiring einsum chain bit-for-bit for f32 (same einsums, same
    ``g·sigmoid(g)`` SiLU)."""
    from repro.models.ffn import _expert_ffn
    E, C, d, f = 4, 24, 16, 32
    params = {
        "we_gate": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1,
                               jnp.float32),
        "we_up": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1,
                             jnp.float32),
        "we_down": jnp.asarray(rng.standard_normal((E, f, d)) * 0.1,
                               jnp.float32),
    }
    b = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    got = _expert_ffn(params, b, use_pallas=False)
    g = jnp.einsum("ecd,edf->ecf", b, params["we_gate"])
    u = jnp.einsum("ecd,edf->ecf", b, params["we_up"])
    legacy = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                        params["we_down"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(legacy))


def test_placement_gmm_fuzz_owner_tables():
    """Hypothesis-style fuzz over random owner tables (replica-heavy,
    single-owner, identity) — gather-free vs gathered bit-identity on
    the kernel, exact equality on the oracle."""
    from repro.kernels.gmm.ops import expert_ffn
    from repro.kernels.gmm.ref import gmm_ref, placement_gmm_ref
    fuzz = np.random.default_rng(7)
    for trial in range(8):
        E = int(fuzz.integers(1, 6))
        S = int(fuzz.integers(1, 10))
        C = int(fuzz.choice([8, 16, 24]))
        d = int(fuzz.choice([16, 32]))
        f = int(fuzz.choice([32, 48]))
        if trial == 0:
            S, owner = E, np.arange(E)              # identity table
        elif trial == 1:
            owner = np.zeros(S, np.int64)           # one hot owner
        else:
            owner = fuzz.integers(0, E, S)
        owner = jnp.asarray(owner, jnp.int32)
        b = jnp.asarray(fuzz.standard_normal((S, C, d)), jnp.float32)
        wg = jnp.asarray(fuzz.standard_normal((E, d, f)) * 0.1,
                         jnp.float32)
        wu = jnp.asarray(fuzz.standard_normal((E, d, f)) * 0.1,
                         jnp.float32)
        wd = jnp.asarray(fuzz.standard_normal((E, f, d)) * 0.1,
                         jnp.float32)
        free = expert_ffn(b, wg, wu, wd, phys_owner=owner,
                          use_pallas=True, interpret=True)
        gathered = expert_ffn(b, wg[owner], wu[owner], wd[owner],
                              use_pallas=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(free),
                                      np.asarray(gathered),
                                      err_msg=f"trial {trial}")
        np.testing.assert_array_equal(
            np.asarray(placement_gmm_ref(b, wg, wu, wd, owner)),
            np.asarray(gmm_ref(b, wg[owner], wu[owner], wd[owner])),
            err_msg=f"trial {trial} (oracle)")


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,KV,hd,L,w", [
    (2, 8, 2, 64, 512, 0), (3, 4, 4, 32, 1024, 0),
    (2, 8, 2, 64, 512, 256), (1, 16, 1, 128, 2048, 0),
    (2, 4, 2, 64, 384, 0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, H, KV, hd, L, w, dtype):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = jnp.asarray(rng.standard_normal((B, H, hd)) * 0.5, dtype)
    k = jnp.asarray(rng.standard_normal((B, L, KV, hd)) * 0.5, dtype)
    v = jnp.asarray(rng.standard_normal((B, L, KV, hd)) * 0.5, dtype)
    lo = min(L, w or L) // 2
    pos = jnp.asarray(rng.integers(lo, (w or L) - 1, B)
                      + (100 if w else 0), jnp.int32)
    got = decode_attention(q, k, v, pos, window=w)
    ref = decode_attention_ref(q, k, v, pos, window=w)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# quant_dispatch
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,d", [(64, 128), (100, 256), (1000, 64), (7, 32)])
def test_quant_dispatch(T, d):
    from repro.kernels.quant_dispatch.ops import fused_quantize
    from repro.kernels.quant_dispatch.ref import quant_dispatch_ref
    x = jnp.asarray(rng.standard_normal((T, d)) * 3, jnp.float32)
    q, s = fused_quantize(x)
    qr, sr = quant_dispatch_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    # round trip error bound: ≤ scale/2 per element
    deq = np.asarray(q, np.float32) * np.asarray(s)[:, None]
    np.testing.assert_allclose(deq, np.asarray(x),
                               atol=float(np.max(np.asarray(s))) * 0.51)


# ---------------------------------------------------------------------------
# route_pack
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,d,k,E,C", [
    (16, 32, 2, 4, 6), (50, 16, 1, 8, 9), (130, 8, 4, 16, 40),
    (7, 128, 8, 3, 20), (1, 4, 1, 1, 4), (257, 64, 3, 12, 11),
])
@pytest.mark.parametrize("quantize", [False, True])
def test_route_pack(T, d, k, E, C, quantize):
    """Interpret-mode Pallas kernel vs jnp oracle: bit-identical buckets,
    scales, eid buckets, ranks and keep masks."""
    from repro.kernels.route_pack.ops import fused_route_pack
    from repro.kernels.route_pack.ref import route_pack_ref
    x = jnp.asarray(rng.standard_normal((T, d)) * 2, jnp.float32)
    N = T * k
    dest = jnp.asarray(rng.integers(0, E, N), jnp.int32)
    valid = jnp.asarray(rng.random(N) > 0.2)
    eid = jnp.asarray(rng.integers(0, 7, N), jnp.int32)
    got = fused_route_pack(x, dest, valid, eid, k=k, n_dest=E, capacity=C,
                           quantize=quantize, use_pallas=True,
                           interpret=True)
    ref = route_pack_ref(x, dest, valid, eid, k=k, n_dest=E, capacity=C,
                         quantize=quantize)
    for name in ("buckets", "scales", "eids", "rank", "keep"):
        g, r = getattr(got, name), getattr(ref, name)
        if g is None:
            assert r is None
            continue
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)


def test_route_pack_no_slot_destination():
    """``dest == n_dest`` takes no slot: the other assignments pack
    exactly as if those rows were absent (kernel and oracle alike)."""
    from repro.kernels.route_pack.ops import fused_route_pack
    from repro.kernels.route_pack.ref import route_pack_ref
    T, d, k, E, C = 40, 16, 2, 4, 6
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    dest = np.asarray(rng.integers(0, E, T * k), np.int32)
    away = rng.random(T * k) < 0.5
    dest[away] = E
    got = fused_route_pack(x, jnp.asarray(dest), jnp.asarray(~away), k=k,
                           n_dest=E, capacity=C, use_pallas=True,
                           interpret=True)
    ref = route_pack_ref(x, jnp.asarray(dest), jnp.asarray(~away), k=k,
                         n_dest=E, capacity=C)
    for name in ("buckets", "rank", "keep"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(ref, name)),
                                      err_msg=name)
    # the kept rows' ranks equal a pack of the remaining rows alone
    here = np.nonzero(~away)[0]
    alone = route_pack_ref(jnp.asarray(np.asarray(x)[here // k]),
                           jnp.asarray(dest[here]), n_dest=E, capacity=C)
    np.testing.assert_array_equal(np.asarray(got.rank)[here],
                                  np.asarray(alone.rank))
    np.testing.assert_array_equal(np.asarray(got.buckets),
                                  np.asarray(alone.buckets))
    assert not np.asarray(got.keep)[away].any()


def test_route_pack_tpu_interpreter(monkeypatch):
    """The row-DMA protocol (one DMA started per kept assignment, one
    semaphore wait per started DMA, counts carried across blocks) under
    the TPU-semantics interpreter with race detection. The interpreter
    takes tens of ms per row, so the block shrinks to 128 rows to cover
    three blocks."""
    from jax.experimental.pallas import tpu as pltpu
    import repro.kernels.route_pack.ops as rp_ops
    from repro.kernels.route_pack.ref import route_pack_ref
    monkeypatch.setattr(rp_ops, "_BLOCK", 128)
    T, d, k, E, C = 50, 128, 6, 8, 24             # 300 rows
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.bfloat16)
    dest = jnp.asarray(rng.integers(0, E, T * k), jnp.int32)
    valid = jnp.asarray(rng.random(T * k) > 0.2)
    got = rp_ops.fused_route_pack(
        x, dest, valid, k=k, n_dest=E, capacity=C, use_pallas=True,
        interpret=pltpu.InterpretParams(detect_races=True))
    ref = route_pack_ref(x, dest, valid, k=k, n_dest=E, capacity=C)
    for name in ("buckets", "rank", "keep"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, name), np.float32),
            np.asarray(getattr(ref, name), np.float32), err_msg=name)


def test_route_pack_bf16_payload():
    from repro.kernels.route_pack.ops import fused_route_pack
    from repro.kernels.route_pack.ref import route_pack_ref
    x = jnp.asarray(rng.standard_normal((24, 16)), jnp.bfloat16)
    dest = jnp.asarray(rng.integers(0, 5, 48), jnp.int32)
    g = fused_route_pack(x, dest, k=2, n_dest=5, capacity=12,
                         use_pallas=True, interpret=True)
    r = route_pack_ref(x, dest, k=2, n_dest=5, capacity=12)
    assert g.buckets.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(g.buckets, np.float32),
                                  np.asarray(r.buckets, np.float32))


# ---------------------------------------------------------------------------
# collect
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,E", [(512, 16), (1000, 64), (4096, 256),
                                 (5, 8)])
def test_collect(N, E):
    from repro.kernels.collect.ops import expert_counts
    from repro.kernels.collect.ref import collect_ref
    ids = jnp.asarray(rng.integers(-1, E, N), jnp.int32)
    got = expert_counts(ids, n_experts=E)
    ref = collect_ref(ids, E)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(np.asarray(got).sum()) == int((np.asarray(ids) >= 0).sum())

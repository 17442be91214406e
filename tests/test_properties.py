"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# EPLB (§4.5)
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    counts=st.lists(
        st.lists(st.integers(0, 1000), min_size=3, max_size=3),
        min_size=4, max_size=16),
    budget=st.integers(0, 4),
)
def test_eplb_never_worse_than_native(counts, budget):
    """Replicating experts must never increase the simulated layer load,
    and replica counts must respect the budget."""
    from repro.serving.eplb import (select_redundant_experts,
                                    simulated_layer_load)
    c = np.asarray(counts, np.int64)           # [E, T]
    chosen = select_redundant_experts(c, budget)
    assert len(chosen) <= budget
    base = simulated_layer_load(c, {e: 1 for e in range(c.shape[0])})
    reps = {e: 1 for e in range(c.shape[0])}
    for e in chosen:
        reps[e] += 1
    assert simulated_layer_load(c, reps) <= base + 1e-9


@settings(max_examples=25, deadline=None)
@given(
    n_exp=st.integers(2, 32),
    budget=st.integers(0, 6),
    n_npus=st.integers(2, 8),
    n_layers=st.integers(1, 4),
    n_tokens=st.integers(1, 128),
    seed=st.integers(0, 1000),
)
def test_placement_table_invariants(n_exp, budget, n_npus, n_layers,
                                    n_tokens, seed):
    """The device-resident placement plane (§4.5): 1) every token
    assignment lands on exactly one physical replica OF ITS ROUTED
    logical expert (owner consistency), 2) round-robin selection keeps a
    duplicated expert's replica loads within one token, 3) budget 0 is
    the identity mapping."""
    from repro.serving.eplb import build_expert_map, build_placement_table
    rng = np.random.default_rng(seed)
    maps = [build_expert_map(rng.integers(0, 500, (n_exp, 4)), n_exp,
                             budget, n_npus) for _ in range(n_layers)]
    t = build_placement_table(maps, n_exp)
    pos = np.arange(n_tokens)
    for li, em in enumerate(maps):
        owner = np.asarray(t.phys_owner[li])
        for e in range(n_exp):
            phys = t.map_assignments(li, pos, np.full(n_tokens, e))
            # 1) one slot per assignment, always a replica of e, owned by e
            assert phys.shape == (n_tokens,)
            assert set(phys.tolist()) <= set(em.replicas[e])
            assert np.all(owner[phys] == e)
            # 2) round-robin balance: replica loads differ by ≤ 1
            loads = np.bincount(phys, minlength=t.n_physical)
            loads = loads[sorted(set(em.replicas[e]))]
            assert loads.max() - loads.min() <= 1
    if budget == 0:
        log = rng.integers(0, n_exp, n_tokens)
        for li in range(n_layers):
            # 3) identity: physical slot == logical expert
            np.testing.assert_array_equal(
                t.map_assignments(li, pos, log), log)


@settings(max_examples=25, deadline=None)
@given(
    n_exp=st.integers(2, 32),
    budget=st.integers(0, 6),
    n_npus=st.integers(2, 8),
    seed=st.integers(0, 1000),
)
def test_expert_map_rotation_covers_replicas(n_exp, budget, n_npus, seed):
    """The rotation table must 1) only reference valid physical slots,
    2) map a logical expert only to its own replicas, 3) visit every
    replica of a hot expert (communication-free balancing)."""
    from repro.serving.eplb import build_expert_map
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 500, (n_exp, 4))
    em = build_expert_map(counts, n_exp, budget, n_npus)
    for e in range(n_exp):
        slots = set(em.replicas[e])
        used = set(int(em.table[p, e]) for p in range(em.rotation_period))
        assert used <= slots
        if len(slots) <= em.rotation_period:
            assert used == slots, "rotation must visit every replica"
    # mapping is a pure gather: vectorized lookup matches the table
    pos = rng.integers(0, 100, 64)
    log = rng.integers(0, n_exp, 64)
    phys = em.map_tokens(pos, log)
    for p, l, f in zip(pos, log, phys):
        assert f == em.table[p % em.rotation_period, l]


@settings(max_examples=25, deadline=None)
@given(
    n_exp=st.integers(2, 16),
    budget=st.integers(0, 5),
    n_npus=st.integers(2, 8),
    ep=st.sampled_from([2, 3, 4, 8]),
    n_tokens=st.integers(1, 96),
    seed=st.integers(0, 1000),
)
def test_sharded_placement_route_invariants(n_exp, budget, n_npus, ep,
                                            n_tokens, seed):
    """Sharded-EP placement routing (§4.5 on a block-sharded slot
    plane): 1) every assignment is claimed by EXACTLY one rank, 2) the
    claiming rank owns a replica slot of the routed logical expert, 3)
    local slot + rank·n_local reconstructs the global round-robin slot,
    4) at budget 0 the padded owner view keeps dead slots unreferenced."""
    from repro.kernels.route_pack.ops import (placement_route,
                                              placement_route_local)
    from repro.serving.eplb import build_expert_map, build_placement_table
    rng = np.random.default_rng(seed)
    em = build_expert_map(rng.integers(0, 500, (n_exp, 4)), n_exp,
                          budget, n_npus)
    t = build_placement_table([em], n_exp)
    n_local = t.slots_per_rank(ep)
    rs, nr, _ = (jnp.asarray(a) for a in t.layer(0))
    dest = jnp.asarray(rng.integers(0, n_exp, n_tokens), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 10_000, n_tokens), jnp.int32)
    phys = np.asarray(placement_route(dest, pos, rs, nr))
    claimed = np.zeros(n_tokens, np.int64)
    for r in range(ep):
        loc, mine = map(np.asarray,
                        placement_route_local(dest, pos, rs, nr, r,
                                              n_local))
        claimed += mine
        for a in np.nonzero(mine)[0]:
            assert r in t.ranks_of_expert(0, int(dest[a]), ep)
            assert r * n_local + loc[a] == phys[a]
    np.testing.assert_array_equal(claimed, np.ones(n_tokens, np.int64))
    # 4) routing only ever targets real replica slots — the identity
    # padding a sharded moe_apply appends can never receive traffic
    assert phys.max(initial=0) < t.n_physical


# ---------------------------------------------------------------------------
# KV block allocator
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["alloc", "free"]),
              st.integers(0, 7), st.integers(1, 300)),
    min_size=1, max_size=60))
def test_allocator_no_leak_no_double_free(ops):
    from repro.serving.kv_cache import (BlockAllocator, DoubleFree,
                                        OutOfBlocks)
    a = BlockAllocator(n_blocks=64, block_size=16)
    live = set()
    for kind, owner, n_tok in ops:
        if kind == "alloc" and owner not in live:
            try:
                blocks = a.allocate(owner, n_tok)
                assert len(blocks) == a.blocks_for(n_tok)
                live.add(owner)
            except OutOfBlocks:
                assert a.free_blocks < a.blocks_for(n_tok)
        elif kind == "free":
            if owner in live:
                a.free(owner)
                live.discard(owner)
            else:
                # double-free / free-of-unknown-owner must raise (and
                # must not change any accounting)
                before = a.free_blocks
                with pytest.raises(DoubleFree):
                    a.free(owner)
                assert a.free(owner, missing_ok=True) == 0
                assert a.free_blocks == before
    for o in list(live):
        a.free(o)
    assert a.free_blocks == 64, "leak detected"
    assert a.usage == 0.0


# ---------------------------------------------------------------------------
# Router / capacity machinery
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 200),
    n_dest=st.integers(1, 16),
    cap=st.integers(1, 32),
    seed=st.integers(0, 10_000),
)
def test_capacity_rank_invariants(n, n_dest, cap, seed):
    """No destination exceeds capacity; kept entries get unique (dest,
    rank) slots; FIFO order preserved."""
    from repro.xccl.routing import capacity_rank
    rng = np.random.default_rng(seed)
    dest = jnp.asarray(rng.integers(0, n_dest, n), jnp.int32)
    rank, keep = capacity_rank(dest, n_dest, cap)
    rank, keep, dest = map(np.asarray, (rank, keep, dest))
    for d in range(n_dest):
        kept = np.sum(keep & (dest == d))
        assert kept <= cap
        ranks = rank[(dest == d) & keep]
        assert sorted(ranks) == list(range(kept)), "ranks must be dense"
    # FIFO: an earlier arrival never has a larger rank than a later one
    for d in range(n_dest):
        rs = rank[dest == d]
        assert all(rs[i] < rs[j] for i in range(len(rs))
                   for j in range(i + 1, len(rs)))


@settings(max_examples=20, deadline=None)
@given(t=st.integers(2, 64), k=st.integers(1, 4), e=st.integers(2, 16),
       seed=st.integers(0, 1000))
def test_router_weights_normalized(t, k, e, seed):
    from repro.models.ffn import _route
    import jax
    k = min(k, e)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (t, 32))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (32, e))
    idx, wts, probs, logits = _route(x, w, k)
    assert idx.shape == (t, k) and wts.shape == (t, k)
    np.testing.assert_allclose(np.asarray(jnp.sum(wts, -1)), 1.0,
                               rtol=1e-5)
    assert int(jnp.max(idx)) < e and int(jnp.min(idx)) >= 0


# ---------------------------------------------------------------------------
# Fused route-pack vs the reference capacity_rank/scatter path
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(
    t=st.integers(1, 40),
    k=st.integers(1, 4),
    e=st.integers(1, 12),
    cap=st.integers(1, 24),
    d=st.integers(1, 48),
    quantize=st.booleans(),
    seed=st.integers(0, 10_000),
)
def test_route_pack_matches_reference_chain(t, k, e, cap, d, quantize,
                                            seed):
    """The fused route-pack kernel (interpret mode) must be bit-identical
    to the live reference path — ``capacity_rank`` + ``quantize_tokens``
    + ``scatter_to_buckets`` from ``xccl.routing`` — for buckets, keep
    masks, ranks and combine weights."""
    import jax
    from repro.kernels.route_pack.ops import fused_route_pack
    from repro.xccl.routing import (capacity_rank, quantize_tokens,
                                    scatter_to_buckets)
    rng = np.random.default_rng(seed)
    n = t * k
    x = jnp.asarray(rng.standard_normal((t, d)) * 3, jnp.float32)
    dest = jnp.asarray(rng.integers(0, e, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) > 0.25)
    w = jnp.asarray(rng.random(n), jnp.float32)     # combine weights

    got = fused_route_pack(x, dest, valid, k=k, n_dest=e, capacity=cap,
                           quantize=quantize, use_pallas=True,
                           interpret=True)

    # live reference chain (exactly what routing.py / ffn.py used to do)
    payload = x[jnp.arange(n) // k]
    rank, keep = capacity_rank(dest, e, cap)
    keep = keep & valid
    if quantize:
        qv, sc = quantize_tokens(payload)
        ref_buckets = scatter_to_buckets(qv, dest, rank, keep, e, cap)
        ref_scales = scatter_to_buckets(sc, dest, rank, keep, e, cap)
        np.testing.assert_array_equal(np.asarray(got.scales),
                                      np.asarray(ref_scales))
    else:
        ref_buckets = scatter_to_buckets(payload, dest, rank, keep, e,
                                         cap)
    np.testing.assert_array_equal(np.asarray(got.buckets),
                                  np.asarray(ref_buckets))
    np.testing.assert_array_equal(np.asarray(got.rank), np.asarray(rank))
    np.testing.assert_array_equal(np.asarray(got.keep), np.asarray(keep))
    # combine weights ride outside the packed payload: masking by the
    # fused keep must equal masking by the reference keep
    np.testing.assert_array_equal(
        np.asarray(jnp.where(got.keep, w, 0.0)),
        np.asarray(jnp.where(keep, w, 0.0)))


# ---------------------------------------------------------------------------
# A2E payload packing (§5.2 disaggregated dispatch)
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(
    t=st.integers(1, 24),
    k=st.integers(1, 4),
    e=st.integers(1, 10),
    cap=st.integers(1, 16),
    d=st.integers(1, 32),
    seed=st.integers(0, 10_000),
)
def test_pack_dispatch_capacity_and_overflow(t, k, e, cap, d, seed):
    """The A2E packer (attention-die side of the MoE-Attention split):
    1) no destination bucket ever exceeds its capacity, 2) every kept
    assignment lands in exactly one bucket slot and carries its token's
    payload, 3) the dropped count is exactly the overflow formula
    ``sum_e max(0, count(e) - capacity)`` (FIFO capacity rank)."""
    from repro.core.moe_attn_disagg import pack_dispatch
    rng = np.random.default_rng(seed)
    hn = jnp.asarray(rng.standard_normal((t, 1, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, e, (t, k)), jnp.int32)
    w = jnp.asarray(rng.random((t, k)), jnp.float32)
    buckets, state = pack_dispatch(hn, idx, w, e, cap, quantize=False)
    flat_idx, rank, keep, tok_of, flat_w = map(np.asarray, state)
    n = t * k
    assert flat_idx.shape == rank.shape == keep.shape == (n,)
    counts = np.bincount(flat_idx, minlength=e)
    # 1) capacity never exceeded, ranks inside the bucket
    for dst in range(e):
        assert int(np.sum(keep & (flat_idx == dst))) <= cap
    assert np.all(rank[keep] >= 0) and np.all(rank[keep] < cap)
    # 2) kept assignments occupy unique (bucket, slot) cells holding
    # their token's row; weights ride outside untouched
    slots = list(zip(flat_idx[keep].tolist(), rank[keep].tolist()))
    assert len(slots) == len(set(slots)), "two tokens in one bucket slot"
    bk = np.asarray(buckets)
    hf = np.asarray(hn.reshape(t, d))
    for a in np.nonzero(keep)[0]:
        np.testing.assert_array_equal(bk[flat_idx[a], rank[a]],
                                      hf[tok_of[a]])
    np.testing.assert_array_equal(flat_w, np.asarray(w).reshape(n))
    # 3) dropped count == the overflow formula
    dropped = int(np.sum(~keep))
    assert dropped == int(np.sum(np.maximum(counts - cap, 0)))


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 4096),
    nd=st.integers(1, 512),
    k=st.integers(1, 8),
    cf=st.floats(0.25, 16.0),
)
def test_chunk_cap_bounds(n, nd, k, cf):
    """Per-chunk bucket capacity: floored at 4, covers a perfectly
    balanced routing whenever the headroom factor is ≥ 1, and is
    monotone in tokens and headroom."""
    from repro.core.moe_attn_disagg import chunk_cap
    cap = chunk_cap(n, nd, k, cf)
    assert cap >= 4
    if cf >= 1.0:
        assert cap >= int(n * k / nd)
    assert chunk_cap(n + 1, nd, k, cf) >= cap
    assert chunk_cap(n, nd, k, cf * 2) >= cap


# ---------------------------------------------------------------------------
# XCCL ring-buffer protocol (§3.1)
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(msgs=st.lists(st.binary(min_size=0, max_size=300_000),
                     min_size=1, max_size=8))
def test_p2p_protocol_fifo_no_loss(msgs):
    from repro.xccl.primitives import make_pair
    a, b, ch = make_pair(ring_slots=64)
    for i, m in enumerate(msgs):
        ch.send(m, event_id=i)
        got = ch.recv(event_id=i)
        assert got == m, "payload corrupted"
        assert ch.acked(i)


def test_p2p_event_id_sanity_and_backpressure():
    from repro.xccl.primitives import XCCLError, make_pair
    a, b, ch = make_pair(ring_slots=2)
    ch.send(b"x", event_id=1)
    ch.recv(event_id=1)
    with pytest.raises(XCCLError):
        ch.send(b"y", event_id=1)        # replayed event
    with pytest.raises(XCCLError):
        ch.send(b"z" * (64 * 1024 * 2 + 1), event_id=2)  # ring full


# ---------------------------------------------------------------------------
# Quantization round trips
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(t=st.integers(1, 64), d=st.integers(1, 256),
       scale=st.floats(0.01, 100.0), seed=st.integers(0, 1000))
def test_tokenwise_quant_error_bound(t, d, scale, seed):
    from repro.xccl.routing import dequantize_tokens, quantize_tokens
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((t, d)) * scale, jnp.float32)
    q, s = quantize_tokens(x)
    back = dequantize_tokens(q, s)
    # symmetric int8: error ≤ scale/2 = amax/254 per element
    bound = np.asarray(s) * 0.51
    assert np.all(np.abs(np.asarray(back - x)) <= bound[:, None] + 1e-6)


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------
@settings(max_examples=30, deadline=None)
@given(toks=st.lists(st.integers(0, 255), min_size=16, max_size=80))
def test_prefix_cache_exact_hit_semantics(toks):
    """Radix semantics of the old exact-hit contract: re-querying an
    inserted prompt matches every full block except the capped last one
    (>= 1 suffix token always prefills), and a diverging final block
    never matches past the common prefix."""
    from repro.serving.kv_cache import PrefixCache
    pc = PrefixCache(capacity_blocks=64, block_size=16)
    stored = pc.insert(
        toks, lambda b, n: [{"start": i * 16} for i in range(b, b + n)])
    n_full = len(toks) // 16
    assert stored == n_full
    if n_full:
        m = pc.match_blocks(toks)
        assert m.n_blocks == max(len(toks) - 1, 0) // 16
        assert m.n_tokens == m.n_blocks * 16 and m.has_payloads
        assert pc.match_fraction(toks) == 1.0
        # a flipped last token diverges only inside its own block: the
        # match never extends past the common block prefix
        other = toks[:-1] + [(toks[-1] + 1) % 256]
        assert pc.match_blocks(other).n_blocks == (len(toks) - 1) // 16
    else:
        assert pc.match_blocks(toks).n_blocks == 0
        assert pc.match_fraction(toks) == 0.0


# ---------------------------------------------------------------------------
# Chunked prefill scheduler (§4.3 token-budget admission over chunks)
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    lens=st.lists(st.integers(1, 3000), min_size=1, max_size=24),
    n_dps=st.integers(1, 4),
    budget=st.integers(64, 4096),
    chunk=st.one_of(st.none(), st.integers(16, 2048)),
)
def test_chunk_scheduler_invariants(lens, n_dps, budget, chunk):
    """1) No chunk exceeds the token budget (or the chunk size), 2)
    every admitted request's chunks are contiguous, non-overlapping and
    cover the whole prompt exactly once, on a single DP, 3) per-DP
    per-step emissions respect the token budget, 4) with the default
    chunk size, budget-sized prompts degenerate to ONE chunk."""
    from repro.serving.request import Request
    from repro.serving.scheduler import PrefillScheduler
    s = PrefillScheduler(n_dps=n_dps, token_budget=budget,
                         chunk_tokens=chunk)
    reqs = [Request(prompt_tokens=[0] * n) for n in lens]
    for r in reqs:
        s.submit(r)
    per_req = {r.req_id: [] for r in reqs}
    req_dp = {}
    for _ in range(1000):
        batches = s.schedule_step()
        for dp, works in enumerate(batches):
            step_toks = 0
            for w in works:
                assert w.n_tokens <= s.token_budget
                assert w.n_tokens <= s.chunk_tokens
                step_toks += w.n_tokens
                per_req[w.req.req_id].append(w)
                req_dp.setdefault(w.req.req_id, dp)
                assert req_dp[w.req.req_id] == dp, \
                    "chunks must stay on the DP holding the partial KV"
            assert step_toks <= s.token_budget
        if not s.pending and not s.queue:
            break
    else:
        raise AssertionError("scheduler did not drain")
    for r in reqs:
        works = per_req[r.req_id]
        assert works, f"prompt of {r.prompt_len} never scheduled"
        assert works[0].start == 0
        for a, b in zip(works, works[1:]):
            assert b.start == a.end, "chunks must be contiguous"
        assert works[-1].end == r.prompt_len, "chunks must cover all"
        assert r.prefill_pos == r.prompt_len
        assert r.n_prefill_chunks == len(works)
        if chunk is None and r.prompt_len <= budget:
            assert len(works) == 1, \
                "budget-sized prompts degenerate to one chunk"

"""Radix prefix cache + paged KV block allocator: property pack.

Invariants of the PR-6 tentpole (radix tree over paged KV blocks):

 * insert-then-match returns the longest common BLOCK prefix (capped
   below the query length — at least one suffix token always prefills),
 * refcounts never go negative; eviction never frees a locked path,
 * eviction frees exactly the blocks insert allocated (no leaks, no
   placeholder sentinel entries consuming capacity),
 * ``BlockAllocator`` conservation under random allocate/extend/free
   (free + used == n_blocks; ``OutOfBlocks`` iff the block formula says
   so; double-free raises),
 * a cancelled mid-chunk prefill releases blocks and radix locks,
 * hit-seeded prefill is indistinguishable from cold prefill (fuzzed
   multi-turn session replay on the cost-model backend; the JAX
   bit-identity gate lives in the slow tier below).

Each property runs two ways: under ``hypothesis`` when the package is
installed (CI), and as a seeded local fuzz loop otherwise — the checks
are shared functions, so both paths exercise identical code.
"""
import numpy as np
import pytest

from repro.serving.kv_cache import (BlockAllocator, DoubleFree, OutOfBlocks,
                                    PodKVDirectory, PrefixCache, RadixTree,
                                    hash_blocks)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # local container: fuzz fallback below
    HAVE_HYPOTHESIS = False

BS = 16


def _lcp_blocks(a, b, bs=BS):
    """Longest common prefix of a and b in FULL blocks."""
    n = 0
    while ((n + 1) * bs <= len(a) and (n + 1) * bs <= len(b)
           and a[n * bs:(n + 1) * bs] == b[n * bs:(n + 1) * bs]):
        n += 1
    return n


# ---------------------------------------------------------------------------
# radix matching: longest common block prefix, capped below the query
# ---------------------------------------------------------------------------
def _check_match_longest(a, b):
    t = RadixTree(capacity_blocks=256, block_size=BS)
    stored = t.insert(list(a))
    assert stored == len(a) // BS
    cap = max(len(b) - 1, 0) // BS
    want = min(_lcp_blocks(a, b), cap)
    m = t.match_blocks(list(b))
    assert m.n_blocks == want and m.n_tokens == want * BS
    assert len(m.payloads) == want
    # read-only fraction is uncapped: the raw longest-cached-prefix
    full = len(b) // BS
    if full:
        assert t.match_fraction(list(b)) == \
            pytest.approx(min(_lcp_blocks(a, b), full) / full)
    # matching never mutates token->payload association: re-match of the
    # inserted prompt itself hits its own (capped) prefix
    m2 = t.match_blocks(list(a))
    assert m2.n_blocks == min(stored, max(len(a) - 1, 0) // BS)


def _check_refcounts_and_eviction(seed):
    """Random insert/match+lock/unlock/evict machine; after every op:
    refs >= 0, allocator conserves blocks, per-node block accounting
    matches the allocator, and eviction never frees a locked path."""
    rng = np.random.default_rng(seed)
    t = RadixTree(capacity_blocks=48, block_size=BS)
    prompts = []
    locked = []          # (nodes, n_blocks_locked)
    for _ in range(rng.integers(20, 60)):
        op = rng.integers(0, 4)
        if op == 0 or not prompts:            # insert (maybe shared prefix)
            if prompts and rng.random() < 0.5:
                base = prompts[rng.integers(len(prompts))]
                toks = base[:rng.integers(0, len(base))] \
                    + rng.integers(2, 60, rng.integers(1, 90)).tolist()
            else:
                toks = rng.integers(2, 60, rng.integers(1, 140)).tolist()
            t.insert(toks)
            prompts.append(toks)
        elif op == 1:                          # match + lock
            q = prompts[rng.integers(len(prompts))]
            m = t.match_blocks(list(q))
            if m.nodes:
                t.lock(m.nodes)
                locked.append((m.nodes, m.n_blocks))
        elif op == 2 and locked:               # unlock
            nodes, _ = locked.pop(rng.integers(len(locked)))
            t.unlock(nodes)
        else:                                  # evict under pressure
            t.evict(int(rng.integers(1, 16)))
            for nodes, _ in locked:
                for n in nodes:                # locked path survives
                    assert n.node_id in t._nodes
        # global invariants
        a = t.allocator
        assert a.free_blocks + a.used_blocks == a.n_blocks
        assert all(n.ref >= 0 for n in t._nodes.values())
        assert sum(len(n.block_ids) for n in t._nodes.values()) \
            == a.used_blocks, "tree blocks must equal allocator usage"
    # teardown: unlock everything, evict all — the pool must come back
    # whole (eviction frees exactly what insert allocated)
    for nodes, _ in locked:
        t.unlock(nodes)
    t.clear()
    assert len(t) == 0
    assert t.allocator.free_blocks == t.allocator.n_blocks


def _check_allocator_ops(seed):
    rng = np.random.default_rng(seed)
    a = BlockAllocator(n_blocks=64, block_size=BS)
    live = set()
    for _ in range(rng.integers(10, 80)):
        op = rng.integers(0, 3)
        owner = int(rng.integers(0, 8))
        if op == 0:
            n_tok = int(rng.integers(1, 400))
            fits = a.blocks_for(n_tok) <= a.free_blocks
            assert a.can_allocate(n_tok) == fits
            if fits:
                blocks = a.allocate(owner, n_tok)
                assert len(blocks) == a.blocks_for(n_tok)
                live.add(owner)
            else:
                with pytest.raises(OutOfBlocks):
                    a.allocate(owner, n_tok)
        elif op == 1:                           # chunk-granular growth
            total = int(rng.integers(1, 500))
            want = max(a.blocks_for(total)
                       - len(a._owned.get(owner, ())), 0)
            if want <= a.free_blocks:
                a.extend(owner, total)
                if a.holds(owner):
                    live.add(owner)
                    assert a.owned_tokens(owner) >= total
            else:
                with pytest.raises(OutOfBlocks):
                    a.extend(owner, total)
        else:
            if owner in live:
                freed = a.free(owner)
                assert freed > 0
                live.discard(owner)
            else:
                with pytest.raises(DoubleFree):
                    a.free(owner)
                assert a.free(owner, missing_ok=True) == 0
        assert a.free_blocks + a.used_blocks == a.n_blocks
        assert a.used_blocks == sum(len(v) for v in a._owned.values())
    for o in list(live):
        a.free(o)
    assert a.free_blocks == 64 and a.usage == 0.0


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(a=st.lists(st.integers(0, 255), min_size=0, max_size=120),
           shared=st.integers(0, 120),
           suffix=st.lists(st.integers(0, 255), min_size=1, max_size=80))
    def test_radix_match_longest_hypothesis(a, shared, suffix):
        _check_match_longest(a, a[:min(shared, len(a))] + suffix)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_radix_refcount_eviction_hypothesis(seed):
        _check_refcounts_and_eviction(seed)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_allocator_conservation_hypothesis(seed):
        _check_allocator_ops(seed)


def test_radix_match_longest_fuzz():
    rng = np.random.default_rng(42)
    for _ in range(40):
        a = rng.integers(0, 255, rng.integers(0, 120)).tolist()
        shared = min(int(rng.integers(0, 120)), len(a))
        b = a[:shared] + rng.integers(0, 255,
                                      rng.integers(1, 80)).tolist()
        _check_match_longest(a, b)


def test_radix_refcount_eviction_fuzz():
    for seed in range(25):
        _check_refcounts_and_eviction(seed)


def test_allocator_conservation_fuzz():
    for seed in range(25):
        _check_allocator_ops(seed)


# ---------------------------------------------------------------------------
# targeted regressions
# ---------------------------------------------------------------------------
def test_allocator_double_free_raises():
    a = BlockAllocator(n_blocks=8, block_size=BS)
    a.allocate(7, 40)
    assert a.free(7) == 3
    with pytest.raises(DoubleFree):
        a.free(7)
    with pytest.raises(DoubleFree):
        a.free(99)                      # never allocated
    assert a.free(99, missing_ok=True) == 0
    assert a.free_blocks == 8


def test_no_placeholder_entries_leak_capacity():
    """Regression: the old exact-hit cache stored a placeholder entry per
    interior prefix, leaking capacity. The radix tree must store exactly
    the prompt's full blocks — interior prefixes are interior NODES,
    never extra payload-bearing entries."""
    calls = []
    toks = list(range(0, 200))          # 12 full blocks + tail
    t = RadixTree(capacity_blocks=64, block_size=BS)
    new = t.insert(toks, lambda b, n: calls.append((b, n))
                   or [{"b": i} for i in range(b, b + n)])
    assert new == len(toks) // BS == 12
    assert t.n_cached_blocks == 12      # capacity == real payload blocks
    assert len(t) == 1                  # one path-compressed edge
    assert calls == [(0, 12)]           # one batched call for the blocks
    # re-inserting the prompt (or any of its prefixes) adds NOTHING
    assert t.insert(toks) == 0
    assert t.insert(toks[:100]) == 0
    assert t.n_cached_blocks == 12 and len(t._nodes) <= 2
    # a divergent prompt splits the edge; block accounting is unchanged
    other = toks[:64] + [250] * 64
    t.insert(other, lambda b, n: [{"b": i} for i in range(b, b + n)])
    assert t.n_cached_blocks == 12 + len(other) // BS - 4
    assert sum(len(n.block_ids) for n in t._nodes.values()) \
        == t.allocator.used_blocks
    assert all(p is not None for n in t._nodes.values()
               for p in n.payloads), "no sentinel payloads"


def test_eviction_never_frees_locked_blocks():
    t = RadixTree(capacity_blocks=8, block_size=BS)
    hot = list(range(0, 64))            # 4 blocks
    t.insert(hot, lambda b, n: [{"b": i} for i in range(b, b + n)])
    m = t.match_blocks(hot + [1])       # uncapped full match of hot
    assert m.n_blocks == 4
    t.lock(m.nodes)
    # pool pressure: a 6-block insert can only take the 4 free blocks
    cold = [200 + i for i in range(96)]
    stored = t.insert(cold,
                      lambda b, n: [{"b": i} for i in range(b, b + n)])
    assert stored == 4 and t.allocator.free_blocks == 0
    # locked path untouched, payloads still served
    m2 = t.match_blocks(hot + [1])
    assert m2.n_blocks == 4 and m2.has_payloads
    t.unlock(m.nodes)
    # now evictable: pressure may reclaim the hot path too
    t.evict(8)
    assert t.allocator.free_blocks == 8 and len(t) == 0


def test_unlock_of_unreferenced_node_raises():
    t = RadixTree(capacity_blocks=8, block_size=BS)
    t.insert(list(range(32)))
    m = t.match_blocks(list(range(33)))
    with pytest.raises(RuntimeError, match="unlock"):
        t.unlock(m.nodes)               # never locked


def test_hit_rate_statistics():
    t = RadixTree(capacity_blocks=64, block_size=BS)
    toks = list(range(64))
    assert t.match_blocks(toks).n_blocks == 0     # miss: 0/4 blocks
    t.insert(toks)
    m = t.match_blocks(toks + [9])                # hit: 4/4 blocks
    assert m.n_blocks == 4
    assert t.n_queries == 2
    assert t.hit_rate == pytest.approx(4 / 8)


def _accounting_state(payloads_fn=None):
    """Two pod-registered trees of 8 blocks through inserts, re-inserts,
    matches, edge splits and evictions: every stored count, node (id,
    start, hashes, block ids, tick, hits, parent), allocator ownership
    and directory publication."""
    pod = PodKVDirectory(block_size=BS)
    trees = [RadixTree(capacity_blocks=8, block_size=BS) for _ in range(2)]
    for owner, t in enumerate(trees):
        pod.register(owner, t)
    rng = np.random.default_rng(7)
    head = rng.integers(2, 60, 64).tolist()
    stored = []
    for k in range(8):
        t = trees[k % 2]
        toks = head[:16 * (k % 4 + 1)] + rng.integers(2, 60, 40).tolist()
        stored.append(t.insert(toks, payloads_fn))
        stored.append(t.match_blocks(toks[:70]).n_blocks)
        stored.append(t.insert(toks[:50], payloads_fn))
    nodes = [[(n.node_id, n.start, n.hashes, n.block_ids, n.tick, n.hits,
               n.parent.node_id) for n in t._nodes.values()]
             for t in trees]
    owned = [dict(t.allocator._owned) for t in trees]
    return stored, nodes, owned, pod._entries


def test_accounting_insert_is_unchanged():
    """An insert without a payload function (the simulator's TE prefix
    directories, accounting-only trees) keeps every hash, block id, tick
    and directory publication: the digest is that of the per-block
    insert the batched one replaced, and a payload-bearing tree walks
    the same states."""
    import hashlib
    state = _accounting_state()
    assert hashlib.sha256(repr(state).encode()).hexdigest() == \
        "a4f624e31fed77b3a6161359ea7a4852dc7f8e390ed95269f9fcf4fed256c430"
    calls = []

    def payloads(first, n):
        calls.append((first, n))
        return [{"b": b} for b in range(first, first + n)]
    assert _accounting_state(payloads) == state
    # one call per insert that stored blocks, none for the others
    assert len(calls) == sum(1 for n in state[0][0::3] + state[0][2::3]
                             if n)


# ---------------------------------------------------------------------------
# JAX backend: the insert's batched slicer (fast tier)
# ---------------------------------------------------------------------------
def _random_cache(m, length, seed=0):
    """A batch-1 cache of the model's layout, filled with noise."""
    import jax
    leaves, treedef = jax.tree.flatten(m.init_cache(1, length))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, x.shape).astype(x.dtype)
        for k, x in zip(keys, leaves)])


@pytest.mark.parametrize("first,n", [(0, 1), (0, 3), (3, 1), (2, 3),
                                     (4, 3), (6, 1)])
@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v3-671b"])
def test_batched_slice_matches_per_block_slices(make_model, arch, first,
                                                n):
    """Each payload of the one-program slicer is bit-identical, in
    pytree, shape and dtype, to the eager per-block slice, for GQA
    (k, v) and MLA (ckv, krope) caches. The 120-token buffer holds 7
    whole blocks and an 8-token tail: (4, 3) and (6, 1) end at its last
    whole block. The payloads are buffers of their own: they survive
    the cache's deletion, as they must survive its donation."""
    import jax
    from repro.serving.backend import JAXBackend
    from repro.xccl.pd_transfer import slice_kv_chunk
    _, m, params = make_model(arch)
    be = JAXBackend(m, params, max_len=256)
    cache = _random_cache(m, 120)
    want = [jax.tree.map(np.asarray,
                         slice_kv_chunk(cache, b * BS, (b + 1) * BS))
            for b in range(first, first + n)]
    got = be.slice_prefill_blocks(cache, [], first, n, BS)
    single = be.slice_prefill_kv(cache, [], first * BS, (first + 1) * BS)
    for leaf in jax.tree.leaves(cache):
        leaf.delete()
    assert len(got) == n
    for w, g in zip(want + want[:1], got + [single]):
        assert jax.tree.structure(g) == jax.tree.structure(w)

        def same(a, b):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(a, np.asarray(b))
        jax.tree.map(same, w, g)


def test_batched_slice_refuses_ranges_that_are_no_blocks(make_model):
    from repro.serving.backend import JAXBackend
    _, m, params = make_model("internlm2-1.8b")
    be = JAXBackend(m, params, max_len=256)
    cache = _random_cache(m, 120)
    with pytest.raises(ValueError, match="whole blocks"):
        be.slice_prefill_blocks(cache, [], 6, 2, BS)   # past block 7
    with pytest.raises(ValueError, match="not a block"):
        be.slice_prefill_kv(cache, [], 8, 24)          # straddles two


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v3-671b"])
def test_seed_from_batched_insert_is_bit_identical(make_model, arch):
    """The TestJAXBitIdentity check driven through DPGroup: the
    provider's prefill stores its blocks by the batched insert, the
    consumer's prefill hits them, seeds and runs the suffix; its logits
    and the valid region of its KV equal a cold chunked prefill's."""
    import jax
    from repro.serving.backend import JAXBackend
    from repro.serving.dp_group import DPGroup
    from repro.serving.request import Request
    from repro.serving.scheduler import ChunkWork
    from repro.xccl.pd_transfer import slice_kv_chunk
    _, m, params = make_model(arch)
    be = JAXBackend(m, params, max_len=256)
    dp = DPGroup(0, be, max_batch=2, max_len=256)
    try:
        rng = np.random.default_rng(2)
        prefix = rng.integers(2, 60, 48).tolist()               # 3 blocks
        provider = prefix + rng.integers(2, 60, 10).tolist()    # bucket 64
        consumer = prefix + rng.integers(2, 60, 70).tolist()    # bucket 128
        pr = Request(prompt_tokens=provider, max_new_tokens=4)
        assert dp.run_prefill_chunk(ChunkWork(pr, 0, len(provider)))
        assert dp.prefix_cache.n_cached_blocks == 3
        cr = Request(prompt_tokens=list(consumer), max_new_tokens=4)
        cache_s, log_s = dp.run_prefill_chunk(
            ChunkWork(cr, 0, len(consumer)))
        assert cr.prefix_hit_tokens == 48
        assert dp.prefix_cache.n_cached_blocks == len(consumer) // BS
        cache_c, log_c = be.prefill_chunk(None, consumer, 0, len(consumer))
        np.testing.assert_array_equal(np.asarray(log_c), log_s)
        kv_c = jax.tree.map(np.asarray,
                            slice_kv_chunk(cache_c, 0, len(consumer)))
        kv_s = jax.tree.map(np.asarray,
                            slice_kv_chunk(cache_s, 0, len(consumer)))
        jax.tree.map(np.testing.assert_array_equal, kv_c, kv_s)
    finally:
        dp.close()


def test_slicer_compiles_once_per_cache_shape(make_model):
    """Prompts of several lengths in one buffer bucket, inserted by one
    DP group and then by a second group's own backend, share one
    executable of the module-level slicer."""
    from repro.serving.backend import JAXBackend
    from repro.serving.dp_group import DPGroup
    from repro.serving.request import Request
    from repro.serving.scheduler import ChunkWork
    from repro.xccl.pd_transfer import slice_kv_blocks
    _, m, params = make_model("internlm2-1.8b")
    dps = [DPGroup(i, JAXBackend(m, params, max_len=256), max_batch=2,
                   max_len=256) for i in range(2)]
    slice_kv_blocks.clear_cache()
    try:
        rng = np.random.default_rng(4)
        for dp in dps:
            for n in (70, 97, 128):                        # bucket 128
                r = Request(prompt_tokens=rng.integers(2, 60, n).tolist(),
                            max_new_tokens=4)
                assert dp.run_prefill_chunk(ChunkWork(r, 0, n))
            assert dp.prefix_cache.n_cached_blocks == (70 + 97 + 128) // BS
            assert slice_kv_blocks._cache_size() == 1
    finally:
        for dp in dps:
            dp.close()


# ---------------------------------------------------------------------------
# DP-group integration (cost-model backend, fast tier)
# ---------------------------------------------------------------------------
def _dp(dp_id=0, **kw):
    from repro.configs import get_config
    from repro.core.transformerless import plan_partition
    from repro.sim.fabric import CostModelBackend, SuperPodCostModel
    cfg = get_config("deepseek-v3-671b")
    cost = SuperPodCostModel(cfg, plan_partition(cfg, 768))
    from repro.serving.dp_group import DPGroup
    return DPGroup(dp_id, CostModelBackend(dp_id, cost), max_batch=2,
                   max_len=4096, n_kv_blocks=512, **kw)


def test_cancel_mid_chunked_prefill_frees_blocks_and_locks():
    from repro.serving.request import Request
    from repro.serving.scheduler import ChunkWork
    dp = _dp()
    try:
        # warm the cache so the cancelled request also holds radix locks
        base = Request(prompt_tokens=list(np.arange(2, 98) % 60))
        dp.run_prefill_chunk(ChunkWork(base, 0, base.prompt_len))
        free0 = dp.allocator.free_blocks
        req = Request(prompt_tokens=base.prompt_tokens + [7] * 64)
        out = dp.run_prefill_chunk(ChunkWork(req, 0, 64))
        assert out is None                       # more chunks pending
        assert dp.allocator.holds(req.req_id)
        assert dp.partial_prefill_cache(req) is not None
        assert any(n.ref > 0 for n in dp.prefix_cache._nodes.values())
        dp.drop_partial_prefill(req)             # cancellation
        assert not dp.allocator.holds(req.req_id)
        assert dp.allocator.free_blocks == free0, "blocks must return"
        assert dp.partial_prefill_cache(req) is None
        assert all(n.ref == 0 for n in dp.prefix_cache._nodes.values()), \
            "radix locks must be released on cancel"
        # the cache itself is intact: a fresh request still hits
        m = dp.prefix_cache.match_blocks(list(base.prompt_tokens))
        assert m.n_blocks > 0
    finally:
        dp.close()


def test_chunk_skip_on_partial_hit_advances_cursor():
    from repro.serving.request import Request
    from repro.serving.scheduler import ChunkWork
    dp = _dp()
    try:
        base = Request(prompt_tokens=[5] * 96)   # 6 full blocks
        dp.run_prefill_chunk(ChunkWork(base, 0, 96))
        chunks0 = dp.backend.n_prefill_chunks
        req = Request(prompt_tokens=[5] * 96 + [9] * 32)
        # first 64-token chunk is fully cached: skipped outright
        assert dp.run_prefill_chunk(ChunkWork(req, 0, 64)) is None
        assert dp.backend.n_prefill_chunks == chunks0, "chunk skipped"
        assert req.prefill_pos == 96 and req.prefix_hit_tokens == 96
        assert dp.backend.n_prefill_seeds == 1
        # scheduler would resume at the jumped cursor: run the suffix
        done = dp.run_prefill_chunk(ChunkWork(req, 96, 32))
        assert done is not None
        _, logits = done
        cold = _dp(dp_id=9)
        try:
            _, ref = cold.run_prefill(
                Request(prompt_tokens=list(req.prompt_tokens)))
            np.testing.assert_array_equal(np.asarray(logits), ref)
        finally:
            cold.close()
    finally:
        dp.close()


def _check_session_replay(seed):
    """Multi-turn session replay: every prompt runs on a warm DP (radix
    hits) and a cold DP (fresh cache) — logits and greedy next tokens
    must be identical."""
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    warm = _dp(dp_id=1, n_cache_blocks=256)
    try:
        convo = rng.integers(2, 60, rng.integers(20, 60)).tolist()
        for _turn in range(4):
            cold = _dp(dp_id=2)
            try:
                _, ref = cold.run_prefill(
                    Request(prompt_tokens=list(convo)))
            finally:
                cold.close()
            r = Request(prompt_tokens=list(convo))
            _, logits = warm.run_prefill(r)
            np.testing.assert_array_equal(logits, ref)
            assert int(np.argmax(logits)) == int(np.argmax(ref))
            if _turn > 0 and len(convo) > 32:
                assert r.prefix_hit_tokens > 0, "warm turn must hit"
            convo = convo + rng.integers(2, 60,
                                         rng.integers(8, 40)).tolist()
    finally:
        warm.close()


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000))
    def test_session_replay_hypothesis(seed):
        _check_session_replay(seed)


def test_session_replay_fuzz():
    for seed in range(6):
        _check_session_replay(seed)


# ---------------------------------------------------------------------------
# JAX backend: hit-seeded prefill is BIT-IDENTICAL to cold (slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestJAXBitIdentity:
    @pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-v3-671b"])
    def test_seeded_prefill_bit_identical(self, make_model, arch):
        """Cold chunked prefill vs radix-hit path (store KV blocks, seed
        a fresh cache, prefill only the suffix): logits AND the valid
        region of the final KV cache must match exactly — the paper's
        prefix cache reuses KV, it must not perturb it. The provider and
        consumer prompts land in different padding buckets on purpose."""
        import jax
        from repro.serving.backend import JAXBackend
        from repro.xccl.pd_transfer import slice_kv_chunk
        _, m, params = make_model(arch)
        be = JAXBackend(m, params, max_len=256)
        assert be.supports_prefix_kv
        rng = np.random.default_rng(0)
        prefix = rng.integers(2, 60, 48).tolist()          # 3 blocks
        provider = prefix + rng.integers(2, 60, 10).tolist()   # bucket 64
        consumer = prefix + rng.integers(2, 60, 70).tolist()   # bucket 128
        cache_p, _ = be.prefill_chunk(None, provider, 0, len(provider))
        payloads = [be.slice_prefill_kv(cache_p, provider, b * 16,
                                        (b + 1) * 16) for b in range(3)]
        cache_c, log_c = be.prefill_chunk(None, consumer, 0,
                                          len(consumer))
        seeded = be.seed_prefill_cache(payloads, 48, len(consumer))
        cache_s, log_s = be.prefill_chunk(seeded, consumer[48:], 48,
                                          len(consumer))
        np.testing.assert_array_equal(np.asarray(log_c),
                                      np.asarray(log_s))
        kv_c = jax.tree_util.tree_map(
            np.asarray, slice_kv_chunk(cache_c, 0, len(consumer)))
        kv_s = jax.tree_util.tree_map(
            np.asarray, slice_kv_chunk(cache_s, 0, len(consumer)))
        jax.tree_util.tree_map(np.testing.assert_array_equal, kv_c, kv_s)

    def test_remote_seeded_prefill_bit_identical(self, make_model):
        """Pod-pooled cross-DP hit: pulling the owner's stored blocks
        through ``read_remote_kv`` (the UB-read step) and seeding from
        the PULLED payloads must stay bit-identical to cold prefill —
        logits and the valid region of the final KV cache."""
        import jax
        from repro.serving.backend import JAXBackend
        from repro.xccl.pd_transfer import slice_kv_chunk
        _, m, params = make_model("internlm2-1.8b")
        be = JAXBackend(m, params, max_len=256)
        rng = np.random.default_rng(1)
        prefix = rng.integers(2, 60, 48).tolist()          # 3 blocks
        provider = prefix + rng.integers(2, 60, 10).tolist()
        consumer = prefix + rng.integers(2, 60, 70).tolist()
        cache_p, _ = be.prefill_chunk(None, provider, 0, len(provider))
        payloads = [be.slice_prefill_kv(cache_p, provider, b * 16,
                                        (b + 1) * 16) for b in range(3)]
        pulled = be.read_remote_kv(payloads)
        cache_c, log_c = be.prefill_chunk(None, consumer, 0,
                                          len(consumer))
        seeded = be.seed_prefill_cache(pulled, 48, len(consumer))
        cache_s, log_s = be.prefill_chunk(seeded, consumer[48:], 48,
                                          len(consumer))
        np.testing.assert_array_equal(np.asarray(log_c),
                                      np.asarray(log_s))
        kv_c = jax.tree_util.tree_map(
            np.asarray, slice_kv_chunk(cache_c, 0, len(consumer)))
        kv_s = jax.tree_util.tree_map(
            np.asarray, slice_kv_chunk(cache_s, 0, len(consumer)))
        jax.tree_util.tree_map(np.testing.assert_array_equal, kv_c, kv_s)

    def test_dp_group_cross_dp_hit_emits_identical_tokens(self,
                                                          make_model):
        """End-to-end through two DPGroups sharing a PodKVDirectory on
        the real JAX backend: a prompt decoded greedily on a cold DP
        and on a DP whose ONLY warm state is another DP's published
        prefix must emit identical token sequences."""
        from repro.serving.backend import JAXBackend
        from repro.serving.dp_group import DPGroup
        from repro.serving.kv_cache import PodKVDirectory
        from repro.serving.request import Request
        _, m, params = make_model("internlm2-1.8b")
        toks = list(np.arange(2, 80) % 60)

        def decode(dp):
            r = Request(prompt_tokens=list(toks), max_new_tokens=8,
                        ignore_eos=True)
            cache1, logits = dp.run_prefill(r)
            dp.admit(r, cache1, logits)
            n0 = len(dp.finished)
            while len(dp.finished) == n0:
                dp.decode_step_all()
            dp.drain()
            return r, list(r.output_tokens)

        pod = PodKVDirectory()
        dp0 = DPGroup(0, JAXBackend(m, params, max_len=256), max_batch=2,
                      max_len=256, pod_directory=pod)
        dp1 = DPGroup(1, JAXBackend(m, params, max_len=256), max_batch=2,
                      max_len=256, pod_directory=pod)
        try:
            _, cold_toks = decode(dp0)      # publishes the prefix
            r2, warm_toks = decode(dp1)     # cross-DP pod hit
            assert dp1.n_remote_hits == 1
            assert r2.prefix_hit_tokens > 0
            assert warm_toks == cold_toks
            assert pod.n_releases == pod.n_remote_acquires
        finally:
            dp0.close()
            dp1.close()

    def test_dp_group_hit_emits_identical_tokens(self, make_model):
        """End-to-end through DPGroup: the same prompt decoded greedily
        on a cold DP and on a warm DP (radix hit) must emit identical
        token sequences."""
        from repro.serving.dp_group import DPGroup
        from repro.serving.backend import JAXBackend
        from repro.serving.request import Request
        _, m, params = make_model("internlm2-1.8b")
        toks = list(np.arange(2, 80) % 60)

        def decode(dp):
            r = Request(prompt_tokens=list(toks), max_new_tokens=8,
                        ignore_eos=True)
            cache1, logits = dp.run_prefill(r)
            dp.admit(r, cache1, logits)
            n0 = len(dp.finished)
            while len(dp.finished) == n0:
                dp.decode_step_all()
            dp.drain()
            return r, list(r.output_tokens)

        dp = DPGroup(0, JAXBackend(m, params, max_len=256), max_batch=2,
                     max_len=256)
        try:
            _, cold_toks = decode(dp)
            r2, warm_toks = decode(dp)      # same prompt: radix hit
            assert r2.prefix_hit_tokens > 0
            assert warm_toks == cold_toks
        finally:
            dp.close()

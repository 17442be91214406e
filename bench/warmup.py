"""Compile, before the window opens, every program shape a mix can
reach, on every DP group (each group's backend holds jitted programs of
its own).

Shapes follow from the prompt lengths a mix sends. The representatives
are, for every power of two, the longest length at or under it and the
shortest over it, so any power-of-two padding scheme meets each of its
buckets once. A mix with a shared head (a system prompt) also reaches
prefill that resumes after a cached prefix: suffixes of every such size
class after the head, and the seeding of a cache from 1 up to every
block count a prompt can hold.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench.traffic.generator import Generator

BLOCK_TOKENS = 16          # the radix cache's block size


def representatives(lengths: List[int]) -> List[int]:
    out = {min(lengths), max(lengths)}
    k = 1
    while k <= max(lengths):
        under = [n for n in lengths if n <= k]
        over = [n for n in lengths if n > k]
        if under:
            out.add(max(under))
        if over:
            out.add(min(over))
        k *= 2
    return sorted(out)


def prompt_lengths(mix: dict, max_len: int, seeds=(0, 1, 2, 3),
                   n: int = 1024) -> List[int]:
    """Prompt lengths as the engine runs them: it keeps the tail of a
    prompt that would leave no room for its output in ``max_len``."""
    lens = set()
    for seed in seeds:
        gen = Generator(mix, seed, 1 << 20)
        for _ in range(n):
            r = gen.next()
            lens.add(min(len(r.prompt), max(max_len - r.max_new - 1, 16)))
    return sorted(lens)


def warm(engine, mix: dict, vocab: int) -> int:
    """Runs each reachable program once per DP group; returns the number
    of program calls made."""
    rng = np.random.default_rng(0)
    lengths = prompt_lengths(mix, engine.max_len)
    head = int(mix.get("system_prompt", 0))
    longest = rng.integers(2, vocab, max(lengths)).tolist()
    calls = 0
    for dp in engine.dps:
        be = dp.backend
        B = dp.max_batch
        # jitted programs compile per input placement too: a second pass
        # feeds write_slot the cache decode returns, and decode the cache
        # write_slot or decode returns, as serving does
        for _ in range(2):
            for n in representatives(lengths):
                cache1, logits = be.prefill_chunk(None, longest[:n], 0, n)
                be.slice_prefill_kv(cache1, longest[:n], 0, BLOCK_TOKENS)
                dp.cache = be.write_slot(dp.cache, cache1, 0)
                calls += 3
            for _ in range(2):
                toks, dp.cache = be.decode_sample(
                    dp.cache, np.zeros((B, 1), np.int32),
                    np.zeros((B,), np.int32), np.zeros((B,), np.float32), 0)
                np.asarray(toks)
                calls += 1
        if not head:
            continue
        top = max(lengths)
        full, _ = be.prefill_chunk(None, longest, 0, top)
        payloads = [be.slice_prefill_kv(full, longest, s, s + BLOCK_TOKENS)
                    for s in range(0, top - BLOCK_TOKENS, BLOCK_TOKENS)]
        calls += 1 + len(payloads)
        for total in representatives([n for n in lengths if n > head]):
            # a hit leaves at least one token to compute: 1 .. (total-1)
            # // BLOCK_TOKENS cached blocks seed a buffer sized for total
            for nb in range(1, (total - 1) // BLOCK_TOKENS + 1):
                be.seed_prefill_cache(payloads[:nb], nb * BLOCK_TOKENS,
                                      total)
                calls += 1
            for n in representatives(list(range(1, total - head + 1))):
                seeded = be.seed_prefill_cache(
                    payloads[:head // BLOCK_TOKENS], head, head + n)
                be.prefill_chunk(seeded, longest[head:head + n], head,
                                 head + n)
                calls += 2
    return calls

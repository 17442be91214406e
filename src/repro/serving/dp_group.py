"""Self-contained DP group (§4.2): one full serving pipeline.

Each DP group owns tokenization, a paged KV allocator, an RTC prefix
cache, proactive GC, and an output-shortcutting worker that detokenizes
and streams tokens straight to the caller — no cross-DP communication
anywhere in the data path. The TE-shell only dispatches requests and
reads status.

Model execution (prefill forward, decode step, cache layout) is behind
an :class:`~repro.serving.backend.ExecutionBackend`: the production
engine injects a jitted :class:`~repro.serving.backend.JAXBackend`, the
SuperPod simulator injects a roofline-derived cost-model backend — the
control plane in this file is identical in both deployments.

The decode hot loop is the zero-sync fast path: ``decode_launch()``
issues the backend's fused decode+sample program (cache donated, async
dispatch) and ``decode_complete()`` fetches only the ``[B]`` int32
next-token vector — 4 bytes per slot crossing device→host per
iteration, never a ``[B, V]`` logits plane (guarded by tests).

When the backend advertises ``mtp_k > 0`` the same loop runs §4.6 MTP
speculative decoding through ``decode_sample_mtp``: each iteration
fetches a ``[B, k+1]`` token block plus a ``[B]`` accepted-count vector
(still O(B) bytes) and a slot may advance 1..k+1 positions per step —
``_apply_sampled_mtp`` emits the accepted prefix token-by-token through
the same output queue, so downstream consumers (streaming watermark,
``Request.n_emitted`` scheduling) see an ordinary variable-rate token
stream.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.serving.backend import ExecutionBackend
from repro.serving.gc_control import ProactiveGC, pin_to_core
from repro.serving.kv_cache import (BlockAllocator, PodKVDirectory,
                                    RadixTree, RemotePin)
from repro.serving.request import Request, RequestState
from repro.serving.scheduler import DPStatus
from repro.serving.spans import span
from repro.serving.tokenizer import EOS, PAD, ByteTokenizer

PyTree = Any


@dataclasses.dataclass
class Slot:
    req: Optional[Request] = None
    next_token: int = PAD
    position: int = 0        # position at which next_token will be written

    @property
    def free(self) -> bool:
        return self.req is None


class DPGroup:
    def __init__(self, dp_id: int, backend: ExecutionBackend, *,
                 max_batch: int = 4, max_len: int = 256,
                 n_kv_blocks: int = 512, block_size: int = 16,
                 n_cache_blocks: Optional[int] = None,
                 gc_every: int = 200, pin_core: Optional[int] = None,
                 pod_directory: Optional[PodKVDirectory] = None):
        self.dp_id = dp_id
        self.backend = backend
        self.max_batch = max_batch
        self.max_len = max_len
        self.tokenizer = ByteTokenizer()
        self.allocator = BlockAllocator(n_kv_blocks, block_size)
        # the radix prefix cache pages its stored KV out of its OWN block
        # pool (default: same size as the request pool), so cached-but-
        # unreferenced KV never counts against live requests in the
        # kv_usage-based DP balancing of §4.3
        self.prefix_cache = RadixTree(
            capacity_blocks=(n_kv_blocks if n_cache_blocks is None
                             else n_cache_blocks),
            block_size=block_size)
        # payload storage/seeding only when the backend can slice KV and
        # resume mid-prompt; otherwise the tree still tracks hit stats
        self._prefix_kv = bool(
            getattr(backend, "supports_prefix_kv", False)
            and backend.supports_chunked_prefill)
        # pod-pooled prefix KV: publish this DP's cached blocks into the
        # pod directory and seed from other DPs' blocks on a remote hit
        # (UB global-shared-memory reads — see PodKVDirectory)
        self.pod_dir = pod_directory if self._prefix_kv else None
        if self.pod_dir is not None:
            self.pod_dir.register(dp_id, self.prefix_cache)
        self.n_remote_hits = 0
        self.remote_hit_blocks = 0
        self.gc_ctl = ProactiveGC(gc_every)
        pin_to_core(pin_core)

        self.slots = [Slot() for _ in range(max_batch)]
        self.cache = backend.init_cache(max_batch, max_len)
        # §4.6 MTP speculative decoding: the backend advertises its draft
        # depth; the group owns the batched draft-head state alongside
        # the main cache (reset per slot at admission)
        self.mtp_k = int(getattr(backend, "mtp_k", 0) or 0)
        self.mtp_cache = (backend.init_mtp_cache(max_batch, max_len)
                          if self.mtp_k else None)
        self.steps = 0
        self.finished: List[Request] = []

        self._sample_key = None   # lazily split jax PRNG (admit sampling)
        self._sample_seed = dp_id
        # zero-sync fast path: in-flight (device tokens, [(slot, req)])
        self._pending: Optional[Tuple[Any, List[Tuple[int, Request]]]] \
            = None
        # EPLB swap deferred while a donated-cache step is in flight
        self._pending_placement: Optional[Any] = None
        self._has_pending_placement = False

        # output shortcutting: dedicated worker streams detokenized output
        self._out_q: "queue.Queue" = queue.Queue()
        self._out_thread = threading.Thread(target=self._output_worker,
                                            daemon=True)
        self._out_thread.start()

        # token-recomputation rollback state (§6.2 stage 3)
        self._rollback: Optional[Dict[str, Any]] = None
        # chunked prefill: req_id → backend-opaque partial-prefill cache
        # (dropped when the final chunk completes or the request leaves)
        self._chunk_caches: Dict[int, PyTree] = {}
        # req_id → locked radix path while the request seeds from it
        self._chunk_locks: Dict[int, List[Any]] = {}
        # req_id → remote pin on another DP's blocks while this request
        # seeds from them over UB (released exactly once: completion or
        # any cancel path pops it through _unlock_chunk)
        self._chunk_pins: Dict[int, RemotePin] = {}

    # ------------------------------------------------------------------
    # output shortcutting worker
    # ------------------------------------------------------------------
    def _output_worker(self) -> None:
        while True:
            item = self._out_q.get()
            if item is None:
                return
            req, token = item
            req.emit(token)

    # ------------------------------------------------------------------
    # prefill path
    # ------------------------------------------------------------------
    def _cache_insert(self, toks: List[int], cache: PyTree) -> None:
        """Store the prompt's full KV blocks in the radix cache. The
        blocks not already cached are sliced in one backend call; without
        prefix-KV backend support the tree is accounting-only (hit
        statistics for TE routing)."""
        if self._prefix_kv:
            bs = self.prefix_cache.block_size
            with span("dp.prefill.insert") as sp:
                sp.n = self.prefix_cache.insert(
                    toks,
                    lambda first, n: self.backend.slice_prefill_blocks(
                        cache, toks, first, n, bs))
        else:
            self.prefix_cache.insert(toks)

    def run_prefill(self, req: Request) -> Tuple[PyTree, np.ndarray]:
        """Returns (batch-1 cache, last-position logits [V]).

        A radix-cache hit seeds a fresh prefill cache from the stored
        block payloads and runs only the un-cached suffix through the
        chunk program (the match is capped below the prompt length, so
        there is always a real forward producing last-token logits)."""
        toks = req.prompt_tokens
        # context clipping: a prompt must leave room for generation inside
        # this DP's cache (production would route it to a long-capable TE;
        # if it still lands here, keep the TAIL of the context)
        limit = max(self.max_len - req.max_new_tokens - 1, 16)
        if len(toks) > limit:
            toks = toks[-limit:]
            req.prompt_tokens = toks
        m = self.prefix_cache.match_blocks(toks) if self._prefix_kv \
            else None
        local = m.n_tokens if (m is not None and m.n_blocks > 0
                               and m.has_payloads) else 0
        pin = self._acquire_remote(toks, local)
        if pin is not None:
            # pod-pooled remote hit: UB-read the owner's blocks and seed;
            # the pin keeps the owner's path eviction-proof for the read
            try:
                payloads = self.backend.read_remote_kv(pin.payloads)
                seeded = self.backend.seed_prefill_cache(
                    payloads, pin.n_tokens, len(toks))
                cache, logits = self.backend.prefill_chunk(
                    seeded, toks[pin.n_tokens:], pin.n_tokens, len(toks))
            finally:
                self.pod_dir.release(pin)
            req.prefix_hit_tokens = max(req.prefix_hit_tokens,
                                        pin.n_tokens)
        elif local > 0:
            self.prefix_cache.lock(m.nodes)
            try:
                seeded = self.backend.seed_prefill_cache(
                    m.payloads, m.n_tokens, len(toks))
                cache, logits = self.backend.prefill_chunk(
                    seeded, toks[m.n_tokens:], m.n_tokens, len(toks))
            finally:
                self.prefix_cache.unlock(m.nodes)
            req.prefix_hit_tokens = max(req.prefix_hit_tokens,
                                        m.n_tokens)
        else:
            cache, logits = self.backend.prefill(toks)
        logits = np.asarray(logits, np.float32)
        self._cache_insert(toks, cache)
        return cache, logits

    def run_prefill_chunk(self, work) -> Optional[Tuple[PyTree,
                                                        np.ndarray]]:
        """Execute one :class:`~repro.serving.scheduler.ChunkWork` via
        the backend's ``prefill_chunk`` contract.

        On the FIRST chunk the radix cache is consulted: a matched block
        prefix seeds the partial prefill cache from stored KV, advances
        ``req.prefill_pos`` past fully-cached chunks (the scheduler then
        emits only suffix chunks), and locks the matched path until the
        prefill completes or is dropped. Blocks are allocated chunk-
        granularly — the request only holds blocks for tokens prefilled
        so far.

        Returns ``(batch-1 cache, last-position logits [V])`` once the
        prompt's prefill COMPLETES (final chunk); ``None`` while chunks
        are still outstanding or when this chunk was skipped entirely
        off a cache hit."""
        req = work.req
        if req.t_prefill_start is None:
            req.t_prefill_start = time.perf_counter()
        with span("dp.prefill", req.req_id) as sp:
            toks = req.prompt_tokens
            # context clipping mirrors run_prefill — engines clip at
            # submit, this is the safety net for direct callers
            limit = max(self.max_len - req.max_new_tokens - 1, 16)
            if len(toks) > limit and work.is_first:
                toks = toks[-limit:]
                req.prompt_tokens = toks
                req.prefill_pos = min(req.prefill_pos, len(toks))
            start = work.start
            if work.is_first:
                self._drop_chunk_state(req)
                if self._prefix_kv:
                    with span("dp.prefill.match"):
                        m = self.prefix_cache.match_blocks(toks)
                        local = m.n_tokens if (m.n_blocks > 0
                                               and m.has_payloads) else 0
                        pin = self._acquire_remote(toks, local)
                    if pin is not None:
                        # pod-pooled remote hit: UB-read the owner's
                        # blocks and seed from them; the pin stays held
                        # (owner path eviction-proof) until the prefill
                        # completes or is dropped — both release through
                        # _unlock_chunk
                        self._chunk_pins[req.req_id] = pin
                        with span("dp.prefill.seed"):
                            payloads = self.backend.read_remote_kv(
                                pin.payloads)
                            self._chunk_caches[req.req_id] = \
                                self.backend.seed_prefill_cache(
                                    payloads, pin.n_tokens, len(toks))
                        req.prefix_hit_tokens = pin.n_tokens
                        self.allocator.extend(req.req_id, pin.n_tokens)
                        if pin.n_tokens >= work.end:
                            req.prefill_pos = max(req.prefill_pos,
                                                  pin.n_tokens)
                            return None
                        start = pin.n_tokens
                    elif local > 0:
                        self.prefix_cache.lock(m.nodes)
                        self._chunk_locks[req.req_id] = m.nodes
                        with span("dp.prefill.seed"):
                            self._chunk_caches[req.req_id] = \
                                self.backend.seed_prefill_cache(
                                    m.payloads, m.n_tokens, len(toks))
                        req.prefix_hit_tokens = m.n_tokens
                        self.allocator.extend(req.req_id, m.n_tokens)
                        if m.n_tokens >= work.end:
                            # whole chunk cached: skip execution, jump
                            # the cursor past every fully-cached chunk
                            req.prefill_pos = max(req.prefill_pos,
                                                  m.n_tokens)
                            return None
                        start = m.n_tokens    # run only the suffix
            end = min(work.end, len(toks))
            chunk = toks[start:end]
            sp.n = len(chunk)
            self.allocator.extend(req.req_id, end)
            cache, logits = self.backend.prefill_chunk(
                self._chunk_caches.pop(req.req_id, None), chunk, start,
                len(toks))
            if work.end >= len(toks):             # prompt complete
                logits = np.asarray(logits, np.float32)
                self._unlock_chunk(req)
                self._cache_insert(toks, cache)
                req.t_prefill_done = time.perf_counter()
                return cache, logits
            self._chunk_caches[req.req_id] = cache
            return None

    def partial_prefill_cache(self, req: Request) -> Optional[PyTree]:
        """The backend-opaque partial-prefill cache of an in-flight
        chunked request (None once complete/absent). PD-disagg slices
        finished chunks out of it to stream KV while later chunks
        compute."""
        return self._chunk_caches.get(req.req_id)

    def _acquire_remote(self, toks: List[int],
                        local_tokens: int) -> Optional[RemotePin]:
        """Pin the best pod-directory prefix STRICTLY longer than the
        local hit (a remote read is only worth its UB traffic when it
        skips compute a local seed would not). Returns a held pin — the
        caller owns its exactly-once release — or None."""
        if self.pod_dir is None:
            return None
        owner, n_blocks = self.pod_dir.match(toks, exclude=self.dp_id)
        if owner is None or \
                n_blocks * self.prefix_cache.block_size <= local_tokens:
            return None
        pin = self.pod_dir.acquire(owner, toks)
        if pin is None:
            return None
        if pin.n_tokens <= local_tokens or not pin.has_payloads:
            self.pod_dir.release(pin)
            return None
        self.n_remote_hits += 1
        self.remote_hit_blocks += pin.n_blocks
        return pin

    @property
    def pooled_hit_rate(self) -> float:
        """Cache hit rate INCLUDING pod-directory remote hits — the
        stat TE routing consumes, so warm-by-proxy DPs aren't
        undercounted (local-only: `prefix_cache.hit_rate`)."""
        c = self.prefix_cache
        return min((c.hit_blocks + self.remote_hit_blocks)
                   / max(c.query_blocks, 1), 1.0)

    def _unlock_chunk(self, req: Request) -> None:
        nodes = self._chunk_locks.pop(req.req_id, None)
        if nodes:
            self.prefix_cache.unlock(nodes)
        pin = self._chunk_pins.pop(req.req_id, None)
        if pin is not None:
            self.pod_dir.release(pin)

    def _drop_chunk_state(self, req: Request) -> None:
        self._chunk_caches.pop(req.req_id, None)
        self._unlock_chunk(req)
        # chunk-granular blocks held by an unfinished prefill go back to
        # the pool (an admitted request's blocks are freed by
        # _finish/evict instead)
        if all(s.req is not req for s in self.slots):
            self.allocator.free(req.req_id, missing_ok=True)

    def drop_partial_prefill(self, req: Request) -> None:
        """Release a partially-prefilled request's chunk cache, radix
        locks and chunk-granular block allocation (failover or
        cancellation) — without this, cancelled requests would strand
        blocks and pin cached subtrees."""
        self._drop_chunk_state(req)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def can_admit(self, req: Request) -> bool:
        has_slot = any(s.free for s in self.slots)
        # chunk-granular allocation means the request may already hold
        # blocks for its prefilled tokens — only the growth must fit
        need = req.prompt_len + req.max_new_tokens
        have = self.allocator.owned_tokens(req.req_id)
        return has_slot and (need <= have
                             or self.allocator.can_allocate(need - have))

    def admit(self, req: Request, cache1: PyTree,
              last_logits: np.ndarray) -> int:
        req.t_admit = time.perf_counter()
        with span("dp.admit", req.req_id):
            slot_id = next(i for i, s in enumerate(self.slots) if s.free)
            self.allocator.extend(req.req_id,
                                  req.prompt_len + req.max_new_tokens)
            self.cache = self.backend.write_slot(self.cache, cache1,
                                                 slot_id)
            if self.mtp_k:
                self.mtp_cache = self.backend.reset_mtp_slot(
                    self.mtp_cache, slot_id)
            first = self._sample(last_logits, req.temperature)
            req.n_emitted += 1
            self._out_q.put((req, int(first)))
            req.state = RequestState.DECODING
            req.slot = slot_id
            req.dp_group = self.dp_id
            self.slots[slot_id] = Slot(req=req, next_token=int(first),
                                       position=req.prompt_len)
        return slot_id

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0.0:
            return int(np.argmax(logits))
        import jax
        if self._sample_key is None:
            self._sample_key = jax.random.PRNGKey(self._sample_seed)
        self._sample_key, sub = jax.random.split(self._sample_key)
        g = np.asarray(jax.random.gumbel(sub, logits.shape))
        return int(np.argmax(logits / temperature + g))

    @property
    def active(self) -> int:
        return sum(0 if s.free else 1 for s in self.slots)

    def active_requests(self) -> List[Request]:
        return [s.req for s in self.slots if not s.free]

    def _gather_step_inputs(self):
        tokens = np.full((self.max_batch, 1), PAD, np.int32)
        positions = np.zeros((self.max_batch,), np.int32)
        temps = np.zeros((self.max_batch,), np.float32)
        active: List[Tuple[int, Request]] = []
        for i, s in enumerate(self.slots):
            if not s.free:
                tokens[i, 0] = s.next_token
                positions[i] = s.position
                temps[i] = s.req.temperature
                active.append((i, s.req))
        return tokens, positions, temps, active

    def _apply_sampled(self, toks: np.ndarray,
                       active: List[Tuple[int, Request]]) -> int:
        """Host bookkeeping for one completed iteration: ``toks`` is the
        ``[B]`` int32 next-token vector from ``decode_sample``."""
        produced = 0
        for i, req_at_launch in active:
            s = self.slots[i]
            if s.free or s.req is not req_at_launch:
                continue        # evicted/replaced between launch+complete
            req = s.req
            tok = int(toks[i])
            s.position += 1
            s.next_token = tok
            produced += 1
            req.n_emitted += 1
            done = (req.n_emitted >= req.max_new_tokens
                    or (tok == req.eos_token and not req.ignore_eos)
                    or s.position >= self.max_len - 1)
            self._out_q.put((req, tok))
            if done:
                self._finish(i)
        self.steps += 1
        self.gc_ctl.step()
        return produced

    def _apply_sampled_mtp(self, blocks: np.ndarray, n_acc: np.ndarray,
                           active: List[Tuple[int, Request]]) -> int:
        """Host bookkeeping for one MTP iteration: slot ``i`` emits
        ``blocks[i, :n_acc[i]+1]`` in order, each token going through the
        same per-token done checks (EOS / budget / buffer edge) as the
        1-token path — a stop mid-block truncates the remaining accepted
        tokens and frees the slot, so the device-side junk beyond it is
        reset at the next admission."""
        produced = 0
        for i, req_at_launch in active:
            s = self.slots[i]
            if s.free or s.req is not req_at_launch:
                continue        # evicted/replaced between launch+complete
            req = s.req
            for j in range(int(n_acc[i]) + 1):
                tok = int(blocks[i, j])
                s.position += 1
                s.next_token = tok
                produced += 1
                req.n_emitted += 1
                done = (req.n_emitted >= req.max_new_tokens
                        or (tok == req.eos_token and not req.ignore_eos)
                        or s.position >= self.max_len - 1)
                self._out_q.put((req, tok))
                if done:
                    self._finish(i)
                    break
        self.steps += 1
        self.gc_ctl.step()
        return produced

    def decode_launch(self) -> bool:
        """Issue one decode iteration without waiting for its result.

        The backend's ``decode_sample`` dispatches asynchronously (JAX:
        the jitted program is enqueued, the cache pytree donated, and
        only a ``[B]`` int32 token handle returned), so the caller can
        launch other DP groups / do host work while the device computes.
        """
        if self.active == 0 or self._pending is not None:
            return False
        with span("dp.decode.launch") as sp:
            with span("dp.decode.inputs"):
                tokens, positions, temps, active = \
                    self._gather_step_inputs()
            sp.n = len(active)
            if self.mtp_k:
                blocks_dev, n_acc_dev, new_cache, new_mtp = \
                    self.backend.decode_sample_mtp(
                        self.cache, self.mtp_cache, tokens, positions,
                        temps, self.steps)
                self.cache = new_cache
                self.mtp_cache = new_mtp
                self._pending = ((blocks_dev, n_acc_dev), active)
                return True
            toks_dev, new_cache = self.backend.decode_sample(
                self.cache, tokens, positions, temps, self.steps)
            self.cache = new_cache
            self._pending = (toks_dev, active)
        return True

    def decode_complete(self) -> int:
        """Fetch the launched iteration's tokens (4·B bytes device→host;
        with MTP 4·B·(k+1) + 4·B) and run the host-side bookkeeping."""
        if self._pending is None:
            return 0
        with span("dp.decode.complete"):
            toks_dev, active = self._pending
            self._pending = None
            if self.mtp_k:
                with span("dp.decode.fetch"):
                    blocks = np.asarray(toks_dev[0])
                    n_acc = np.asarray(toks_dev[1])
                with span("dp.decode.apply"):
                    produced = self._apply_sampled_mtp(blocks, n_acc,
                                                       active)
            else:
                with span("dp.decode.fetch"):
                    toks = np.asarray(toks_dev)
                with span("dp.decode.apply"):
                    produced = self._apply_sampled(toks, active)
            if self._has_pending_placement:
                # deferred EPLB swap: the donated-cache step has retired,
                # so the placement can change before the next launch
                # (§4.5 reconfiguration never lands mid-iteration)
                table = self._pending_placement
                self._pending_placement = None
                self._has_pending_placement = False
                self.backend.apply_placement(table)
        return produced

    # ------------------------------------------------------------------
    # EPLB placement swap (§4.5 step 3, the "swap" phase)
    # ------------------------------------------------------------------
    def apply_placement(self, table: Optional[Any]) -> None:
        """Install a new expert placement on this group's backend. If a
        donated-cache decode step is in flight, the swap is deferred to
        the ``decode_complete`` boundary (the reconfiguration contract:
        placement never changes mid-iteration)."""
        if self._pending is not None:
            self._pending_placement = table
            self._has_pending_placement = True
            return
        self.backend.apply_placement(table)

    def decode_step_all(self, inject_fault: bool = False) -> int:
        """One engine iteration over all active slots. Returns number of
        tokens produced. ``inject_fault`` exercises the §6.2 token-
        recomputation path: the step is rolled back and re-executed (on
        the undonated safe path, which keeps the pre-step cache alive)."""
        if self.active == 0:
            return 0
        if not inject_fault:
            self.decode_launch()
            return self.decode_complete()
        tokens, positions, temps, active = self._gather_step_inputs()
        # save rollback state (previous iteration boundary); donation is
        # off so the pre-step cache handle stays valid for re-execution.
        # With MTP the draft-head state rolls back alongside the main
        # cache — same step ⇒ same PRNG draws ⇒ identical re-execution.
        self._rollback = {"cache": self.cache,
                          "mtp_cache": self.mtp_cache,
                          "slots": [dataclasses.replace(s)
                                    for s in self.slots]}
        if self.mtp_k:
            self.backend.decode_sample_mtp(
                self.cache, self.mtp_cache, tokens, positions, temps,
                self.steps, donate=False)
        else:
            self.backend.decode_sample(self.cache, tokens, positions,
                                       temps, self.steps, donate=False)
        # §6.2: transient network error detected → all DP groups roll
        # back to the previous iteration and re-execute.
        self.cache = self._rollback["cache"]
        self.mtp_cache = self._rollback["mtp_cache"]
        self.slots = self._rollback["slots"]
        if self.mtp_k:
            blocks, n_acc, new_cache, new_mtp = \
                self.backend.decode_sample_mtp(
                    self.cache, self.mtp_cache, tokens, positions, temps,
                    self.steps, donate=False)
            self.cache = new_cache
            self.mtp_cache = new_mtp
            return self._apply_sampled_mtp(np.asarray(blocks),
                                           np.asarray(n_acc), active)
        toks, new_cache = self.backend.decode_sample(
            self.cache, tokens, positions, temps, self.steps,
            donate=False)
        self.cache = new_cache
        return self._apply_sampled(np.asarray(toks), active)

    def _finish(self, slot_id: int) -> None:
        s = self.slots[slot_id]
        req = s.req
        self.allocator.free(req.req_id)
        req.t_finished = time.perf_counter()
        req.state = RequestState.FINISHED
        self.finished.append(req)
        self.slots[slot_id] = Slot()

    def evict(self, slot_id: int) -> Optional[Request]:
        """Pull a request out of a slot without finishing it (dead-DP
        failover, §6.2: the TE-shell re-dispatches it elsewhere)."""
        s = self.slots[slot_id]
        if s.free:
            return None
        req = s.req
        self.allocator.free(req.req_id)
        self.slots[slot_id] = Slot()
        req.state = RequestState.QUEUED
        req.slot = None
        req.dp_group = None
        return req

    # ------------------------------------------------------------------
    def status(self) -> DPStatus:
        return DPStatus(
            dp_id=self.dp_id,
            batch_size=self.max_batch,
            active=self.active,
            kv_usage=self.allocator.usage,
            kv_free_blocks=self.allocator.free_blocks,
            block_size=self.allocator.block_size,
        )

    def drain(self) -> None:
        while not self._out_q.empty():
            time.sleep(0.001)

    def close(self) -> None:
        self._out_q.put(None)
        self.gc_ctl.close()

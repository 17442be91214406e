"""Execution backends: the seam between serving control plane and model
execution.

A :class:`DPGroup` owns admission, KV accounting, prefix caching, slot
management and sampling — but the actual forward passes (prefill, decode
step) and the cache representation go through an :class:`ExecutionBackend`.
Two implementations exist:

  * :class:`JAXBackend` — the production path: jitted SPMD executors over
    a built ``Model`` + params (what FlowServe deploys on real devices).
  * ``repro.sim.fabric.CostModelBackend`` — the SuperPod simulator's
    path: no tensors, deterministic pseudo-logits, and an analytic
    roofline/XCCL cost model supplying iteration latencies so the full
    scheduler/EPLB/reliability stack can be exercised at 384-die scale
    on one CPU in seconds.

Keeping the cache pytree opaque to the DPGroup (``init_cache`` /
``write_slot`` live here) is what lets the simulated backend use a
zero-byte cache object while the JAX backend uses the real layer-stacked
decode cache.

The ``decode_sample`` contract — the zero-sync decode fast path
---------------------------------------------------------------

``decode_sample(cache, tokens, positions, temperatures, step)`` runs ONE
decode iteration **and** the token sampling in a single backend-side
program, returning ``(next_tokens, new_cache)`` where ``next_tokens`` is
an ``[B]`` int32 array (device-resident for :class:`JAXBackend` — the
caller fetches it when needed, so dispatch is asynchronous and the
transfer is 4 bytes/slot instead of a ``[B, V]`` f32 logits plane).
Contract details every implementation must honor:

* ``tokens`` int32 ``[B, 1]``, ``positions`` int32 ``[B]``,
  ``temperatures`` f32 ``[B]`` (``<= 0`` ⇒ greedy per slot), ``step`` an
  int identifying the engine iteration — the PRNG stream is a pure
  function of ``(backend seed, step)`` so replays are deterministic.
* The returned ``new_cache`` replaces the caller's handle. With
  ``donate=True`` (default) the JAX path donates the cache pytree to the
  XLA executable (``donate_argnums``), so KV is updated in place and the
  *old* handle must never be reused; callers that need the previous
  cache for §6.2 rollback/re-execution pass ``donate=False``.
* Host traffic per step must stay ≤ ``4 * B`` bytes (token ids only) —
  guarded by tests; the legacy ``decode`` (full-logits) entry remains
  for diagnostics and for callers that genuinely need distributions.

The ``decode_sample_mtp`` contract — speculative decoding (§4.6)
----------------------------------------------------------------

``decode_sample_mtp(cache, mtp_cache, tokens, positions, temperatures,
step)`` is the multi-token sibling of ``decode_sample``: ONE dispatch
runs the MTP draft head ``k = mtp_k`` times (chained through its own
decode cache), the main model's verify forward over ``[token, draft_1,
…, draft_k]`` (``k + 1`` decode-shaped steps — identical op shapes to
``decode_sample``), and on-device acceptance sampling
(:func:`repro.serving.sampling.speculative_verify`). It returns
``(token_block [B, k+1] int32, n_accepted [B] int32, new_cache,
new_mtp_cache)``; slot ``i`` emits ``token_block[i, :n_accepted[i]+1]``
and entries past that are junk. Contract details on top of
``decode_sample``'s:

* Host traffic stays O(B): ``4·B·(k+1)`` bytes of token ids plus ``4·B``
  bytes of accepted counts — never logits (guard-tested like the 1-token
  path).
* Acceptance semantics: greedy slots (``temperature <= 0``) accept a
  draft iff it equals the main model's argmax, and every emitted token
  IS that argmax — the emitted stream is bit-identical to
  non-speculative greedy decode (lossless, guard-tested on the
  deepseek-v3 smoke config). Stochastic slots use the standard
  rejection rule (accept ``d ~ q`` w.p. ``min(1, p(d)/q(d))``, resample
  rejections from ``norm(max(p-q, 0))``), so each emitted token is
  distributed exactly as the main model's ``p``.
* Donation/rollback: ``donate=True`` (default) donates BOTH ``cache``
  and ``mtp_cache`` to the executable; the §6.2 rollback path passes
  ``donate=False`` and must snapshot *both* handles — re-executing an
  iteration with the same ``step`` replays the identical draft,
  acceptance and resample draws (the PRNG stream is still a pure
  function of ``(backend seed, step)``).
* Main-cache discipline: the verify chain writes KV at ``positions + j``
  for ``j <= k`` (clamped to the buffer). Rejected positions hold junk
  that decode attention never reads (it masks ``kv_pos <= q_pos``) and
  that the next iteration overwrites before it can ever be attended.
  The same argument covers the draft head's cache; admission resets a
  slot's MTP state via ``reset_mtp_slot`` (the ``write_slot`` analogue).
* ``mtp_cache`` is backend-opaque batched draft-head state created by
  ``init_mtp_cache`` — on the JAX path ``{"kv": block decode cache,
  "hidden": [B, 1, d]}``, the hidden being the main-model final hidden
  carried between iterations as the head's conditioning input.
* Backends advertise the feature with ``mtp_k > 0``; the 1-token
  ``decode_sample`` contract is unchanged and remains the default path.

The ``prefill_chunk`` contract — chunked prefill
------------------------------------------------

``prefill_chunk(cache, tokens, offset, total_len)`` runs ONE contiguous
chunk of a prompt's prefill and returns ``(cache, logits)``. It is the
execution half of the chunk-granular prefill path: the
:class:`~repro.serving.scheduler.PrefillScheduler` emits token-budget
:class:`~repro.serving.scheduler.ChunkWork` slices, ``DPGroup`` executes
them through this entry, and the KV built so far can stream to a decode
TE chunk by chunk (``xccl/pd_transfer.py``) while later chunks compute.
Contract details:

* ``cache`` is backend-opaque partial-prefill state: pass ``None`` on
  the first chunk (``offset == 0``) — the backend allocates it sized
  for ``total_len`` — and thereafter the handle returned by the
  previous chunk. The caller must feed chunks back-to-back and in
  order (``offset`` equals the sum of prior chunk lengths).
* ``tokens`` is the chunk's token list, ``total_len`` the full prompt
  length (so the backend can bucket the buffer once and knows which
  chunk is final).
* ``logits`` is the last-valid-position logits ``[V]`` of the chunk for
  backends that compute incrementally, and MUST equal ``prefill``'s
  last-position logits on the final chunk; backends without incremental
  execution may return ``None`` for non-final chunks.
* On :class:`JAXBackend` the chunked path is BIT-IDENTICAL to the
  monolithic ``prefill`` on the valid region: same logits on the final
  chunk, same KV cache at positions ``< total_len`` (positions beyond
  hold padding junk in both paths and are masked by decode). One
  chunk-shaped jitted program per (chunk bucket, buffer bucket) pair is
  reused across chunks and requests via padding buckets, with the
  offset traced.
* ``supports_chunked_prefill`` advertises true incremental execution
  (global-attention decoder-only stacks on the JAX path; always true
  for the sim backend, which counts chunks). When false, the default
  implementation buffers tokens and runs one monolithic ``prefill`` at
  the final chunk — chunk SCHEDULING still applies, execution cost
  does not split.

The prefix-KV contract — radix-cache seeding
--------------------------------------------

``slice_prefill_kv(cache, tokens, start, end)`` extracts the KV payload
of token range ``[start, end)`` from a completed (or partial) batch-1
prefill cache, and ``seed_prefill_cache(payloads, prefix_len, total_len)``
rebuilds a partial-prefill cache whose first ``prefix_len`` positions
hold those payloads — the handle it returns is what ``prefill_chunk``
accepts at ``offset == prefix_len``. Together they are the storage/reuse
half of the radix-tree prefix cache (``serving/kv_cache.py``): on insert
the :class:`~repro.serving.dp_group.DPGroup` stores one payload per KV
block, and on a partial hit it seeds a fresh cache from the stored
blocks so only the un-cached suffix runs through the chunk programs.
Contract details:

* ``slice_prefill_blocks(cache, tokens, first_block, n_blocks,
  block_size)`` is the insert's batched slice: the payloads of blocks
  ``[first_block, first_block + n_blocks)``, block ``b`` covering tokens
  ``[b * block_size, (b + 1) * block_size)``, each equal to what
  ``slice_prefill_kv`` gives for that range. The radix insert makes one
  such call for all of a prompt's new blocks. The base class loops over
  ``slice_prefill_kv``. :class:`JAXBackend` runs one jitted program per
  cache shape (``xccl/pd_transfer.slice_kv_blocks``) that cuts every
  whole block of the buffer and keeps the ones asked for; its
  ``slice_prefill_kv`` takes one block (``start`` a multiple of the
  range's width) from the same program, so one warm-up call per cache
  shape compiles the insert's slicer too.
* Payloads must be fresh buffers, never views or aliases of ``cache``:
  the chunk programs donate their cache, and the radix tree holds the
  payloads past that.
* ``payloads`` is a list of consecutive block slices (as produced by
  ``slice_prefill_kv``) covering ``[0, prefix_len)`` in order.
* The seeded cache must make a subsequent
  ``prefill_chunk(seeded, tokens[prefix_len:], prefix_len, total_len)``
  BIT-IDENTICAL to the cold chunked prefill of the same prompt — same
  final logits, same KV on the valid region. On :class:`JAXBackend`
  the payload slices are fresh arrays (never the donated chunk buffer)
  and seeding writes them into a fresh ``init_cache`` buffer, so the
  donation discipline of ``prefill_chunk`` is preserved.
* ``supports_prefix_kv`` gates the whole path: it requires
  ``supports_chunked_prefill`` (seeding continues mid-prompt) and a
  seq-addressed cache layout (``xccl/pd_transfer.py`` slicing). When
  False, the radix tree still tracks hit statistics for scheduler
  routing, but no KV is stored and no compute is skipped.
* **Cross-DP reads (pod-pooled prefix KV).** The payloads fed to
  ``seed_prefill_cache`` need not come from the seeding DP's own radix
  tree: with a :class:`~repro.serving.kv_cache.PodKVDirectory` wired
  in, a DP that misses locally can pin another DP's cached prefix
  (``PodKVDirectory.acquire`` → ``RemotePin``) and pull the stored
  blocks through ``read_remote_kv`` — the UB global-shared-memory read
  of ``xccl/pd_transfer.ub_read``, a one-sided copy that involves no
  compute on the owner. The read returns fresh arrays bit-identical to
  the owner's stored payloads, so a remote-hit-seeded prefill obeys the
  same bit-identity clause as a local hit: equal to the cold chunked
  prefill on final logits AND valid-region KV. The owner's blocks stay
  pinned (refcount-locked, eviction-proof) from ``acquire`` until the
  borrower releases the pin — on prefill completion or on any cancel
  path (``DPGroup.drop_partial_prefill``), exactly once.

The ``apply_placement`` contract — the EPLB data plane
------------------------------------------------------

``apply_placement(table)`` installs a device-resident
:class:`~repro.serving.eplb.PlacementTable` (stacked per-layer
logical→physical expert slot maps) that every subsequent decode
iteration routes through. It is the *swap* phase of the §4.5 live
reconfiguration: the reconfigurator prefetches and shadow-loads replica
weights first, then calls this between decode iterations. Callers must
never invoke it while a donated-cache ``decode_sample`` is in flight —
:class:`~repro.serving.dp_group.DPGroup.apply_placement` defers the
swap to the next ``decode_complete`` boundary for exactly this reason.
``apply_placement(None)`` reverts to logical routing. Implementations
should keep table shapes stable across swaps (the builder's
``pad_physical``/``pad_replicas``) so the jitted decode program is
reused rather than retraced.
"""
from __future__ import annotations

import abc
from typing import Any, List, Optional, Tuple

import numpy as np

from repro.serving.spans import span

PyTree = Any


class ExecutionBackend(abc.ABC):
    """Model-execution contract consumed by :class:`DPGroup`."""

    #: vocab size of the logits this backend produces.
    vocab_size: int

    @abc.abstractmethod
    def init_cache(self, max_batch: int, max_len: int) -> PyTree:
        """Allocate the decode cache for ``max_batch`` slots."""

    @abc.abstractmethod
    def prefill(self, tokens: List[int]) -> Tuple[PyTree, np.ndarray]:
        """Run the prefill forward for one prompt.

        Returns ``(batch-1 cache, last-position logits [V])``.
        """

    #: True when ``prefill_chunk`` executes incrementally (per-chunk
    #: compute + streamable partial KV); False ⇒ the default buffering
    #: fallback below.
    supports_chunked_prefill: bool = False

    def prefill_chunk(self, cache: Optional[PyTree], tokens: List[int],
                      offset: int, total_len: int
                      ) -> Tuple[PyTree, Optional[np.ndarray]]:
        """Run one contiguous prefill chunk — see the module docstring.

        Default implementation: accumulate the chunk tokens and run the
        monolithic :meth:`prefill` once the final chunk arrives (for
        backends whose architectures cannot prefill incrementally, e.g.
        recurrent-state caches)."""
        if cache is None:
            if offset != 0:
                raise ValueError("first chunk must start at offset 0")
            cache = {"_chunk_tokens": []}
        buf = cache["_chunk_tokens"]
        if offset != len(buf):
            raise ValueError(
                f"non-contiguous chunk: offset {offset} != {len(buf)}")
        buf.extend(tokens)
        if len(buf) >= total_len:
            return self.prefill(buf)
        return cache, None

    #: True when the backend can slice per-block KV payloads out of a
    #: prefill cache and seed a new partial-prefill cache from them —
    #: see the prefix-KV contract in the module docstring.
    supports_prefix_kv: bool = False

    def slice_prefill_kv(self, cache: PyTree, tokens: List[int],
                         start: int, end: int) -> PyTree:
        """Extract the KV payload for token range ``[start, end)`` from a
        batch-1 prefill cache (``tokens`` is the full prompt — backends
        whose cache has no per-position content, like the sim's cost
        model, derive the payload from the token range instead)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support prefix-KV slicing")

    def slice_prefill_blocks(self, cache: PyTree, tokens: List[int],
                             first_block: int, n_blocks: int,
                             block_size: int) -> List[PyTree]:
        """The payloads of blocks ``[first_block, first_block +
        n_blocks)`` of ``block_size`` tokens each, in one call — see the
        prefix-KV contract in the module docstring. Default: one
        ``slice_prefill_kv`` per block."""
        return [self.slice_prefill_kv(cache, tokens, b * block_size,
                                      (b + 1) * block_size)
                for b in range(first_block, first_block + n_blocks)]

    def seed_prefill_cache(self, payloads: List[PyTree], prefix_len: int,
                           total_len: int) -> PyTree:
        """Build a partial-prefill cache whose ``[0, prefix_len)`` region
        holds the given consecutive block payloads; the result is valid
        ``prefill_chunk`` input at ``offset == prefix_len``."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support prefix-KV seeding")

    def read_remote_kv(self, payloads: List[PyTree]) -> List[PyTree]:
        """Pull another DP's stored block payloads over UB global shared
        memory (the cross-DP read step of the pod-pooled prefix cache —
        see the prefix-KV contract in the module docstring). The result
        feeds ``seed_prefill_cache`` exactly like locally stored blocks
        and must be bit-identical to the owner's payloads. The default
        routes through ``xccl/pd_transfer.ub_read`` (one-sided copy;
        non-array payloads pass through), which every prefix-KV backend
        can use as-is."""
        from repro.xccl.pd_transfer import ub_read
        return [ub_read(p) for p in payloads]

    @abc.abstractmethod
    def write_slot(self, cache: PyTree, cache1: PyTree,
                   slot: int) -> PyTree:
        """Insert a batch-1 prefill cache into batch slot ``slot``."""

    @abc.abstractmethod
    def decode(self, cache: PyTree, tokens: np.ndarray,
               positions: np.ndarray) -> Tuple[np.ndarray, PyTree]:
        """One decode step over all slots (diagnostic / logits path).

        ``tokens``: int32 [B, 1]; ``positions``: int32 [B].
        Returns ``(logits [B, V], new cache)``.
        """

    @abc.abstractmethod
    def decode_sample(self, cache: PyTree, tokens: np.ndarray,
                      positions: np.ndarray, temperatures: np.ndarray,
                      step: int, *, donate: bool = True
                      ) -> Tuple[Any, PyTree]:
        """One decode iteration + on-device sampling (fast path).

        Returns ``(next_tokens [B] int32, new cache)`` — see the module
        docstring for the full contract.
        """

    #: number of MTP draft tokens per decode iteration; 0 ⇒ speculative
    #: decoding disabled (``decode_sample_mtp`` unavailable).
    mtp_k: int = 0

    def init_mtp_cache(self, max_batch: int, max_len: int) -> PyTree:
        """Allocate the batched MTP draft-head state (``mtp_k > 0``)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support MTP decoding")

    def reset_mtp_slot(self, mtp_cache: PyTree, slot: int) -> PyTree:
        """Zero slot ``slot`` of the draft-head state at admission — the
        ``write_slot`` analogue for ``mtp_cache``. Returns the new
        handle (the old one may be donated)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support MTP decoding")

    def decode_sample_mtp(self, cache: PyTree, mtp_cache: PyTree,
                          tokens: np.ndarray, positions: np.ndarray,
                          temperatures: np.ndarray, step: int, *,
                          donate: bool = True
                          ) -> Tuple[Any, Any, PyTree, PyTree]:
        """One propose-then-verify MTP iteration in a single dispatch.

        Returns ``(token_block [B, mtp_k+1] int32, n_accepted [B] int32,
        new_cache, new_mtp_cache)`` — see the module docstring for the
        full contract (acceptance semantics, donation/rollback, host
        transfer budget)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support MTP decoding")

    def apply_placement(self, table: Optional[Any]) -> None:
        """Install the EPLB :class:`~repro.serving.eplb.PlacementTable`
        subsequent decode iterations route through (``None`` ⇒ logical
        routing). Must only be called between decode iterations — see
        the module docstring. Default: no-op (backends without an
        expert data plane)."""


# ---------------------------------------------------------------------------
# Production backend: jitted JAX executors
# ---------------------------------------------------------------------------
def _bucket_len(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 2048) * 2048


class JAXBackend(ExecutionBackend):
    """Graph-mode decode + bucketed-length prefill over a built model.

    The decode hot loop is :meth:`decode_sample`: forward + sampling in
    one jitted program with the cache pytree donated, so each iteration
    updates KV in place and returns only ``[B]`` int32 token ids.
    """

    def __init__(self, model, params: PyTree, *, max_len: int = 256,
                 memory: Optional[Any] = None, seed: int = 0,
                 top_k: int = 0, mtp_k: int = 0):
        import jax

        from repro.serving.sampling import sample_tokens

        self.model = model
        self.params = params
        self.max_len = max_len
        self.memory = memory
        self.seed = seed
        self.top_k = top_k
        self.mtp_k = int(mtp_k)
        if self.mtp_k and "mtp" not in params:
            raise ValueError(
                f"mtp_k={mtp_k} requires a model with an MTP head "
                f"(cfg.mtp_num_layers > 0)")
        self.vocab_size = model.cfg.vocab_size
        self._decode = jax.jit(model.decode_step)
        self._prefill = jax.jit(model.prefill, static_argnames=())
        # chunked prefill: one program per (chunk bucket, buffer bucket)
        # shape pair, offset and last_pos traced so every chunk of every
        # request reuses the compiled executable; the cache buffer is
        # donated so each chunk writes its K/V in place (the old handle
        # is replaced by the returned one, like decode_sample's cache)
        self._prefill_chunk = jax.jit(model.prefill_chunk,
                                      donate_argnums=(1,))
        # EPLB data plane: the active PlacementTable (None ⇒ logical
        # routing). Swapped by apply_placement between decode steps;
        # passed into the jitted programs as a traced pytree so swaps
        # with stable shapes reuse the compiled executable.
        self._placement = None

        import jax.numpy as jnp

        self._base_key = jax.random.PRNGKey(seed)

        def _step(params, cache, tokens, positions, temperatures,
                  base_key, step, placement, stochastic):
            logits, new_cache = model.decode_step(params, cache, tokens,
                                                  positions,
                                                  placement=placement)
            with jax.named_scope("sampling"):
                if stochastic:
                    key = jax.random.fold_in(base_key, step)
                    toks = sample_tokens(logits, temperatures, key,
                                         top_k=self.top_k)
                else:
                    toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return toks, new_cache

        # donated fast path (in-place KV) + undonated safe path (the §6.2
        # rollback keeps a live handle to the pre-step cache); greedy
        # batches compile without the Gumbel draw
        self._decode_sample = jax.jit(_step, donate_argnums=(1,),
                                      static_argnames=("stochastic",))
        self._decode_sample_safe = jax.jit(
            _step, static_argnames=("stochastic",))
        self._write_slot = jax.jit(self._write_slot_impl,
                                   donate_argnums=(0,))

        if self.mtp_k:
            from repro.serving.sampling import speculative_verify

            k = self.mtp_k
            max_pos = max_len - 1

            def _mtp_step(params, cache, mtp_cache, tokens, positions,
                          temperatures, base_key, step, placement,
                          stochastic):
                """Propose-then-verify in one program — see the
                ``decode_sample_mtp`` module-docstring contract."""
                key = jax.random.fold_in(base_key, step)
                k_draft, k_verify = jax.random.split(key)
                hid, mtp_kv = mtp_cache["hidden"], mtp_cache["kv"]

                # draft chain: the single head re-applied k times on its
                # own hidden (the paper's reused-weights deep drafting),
                # each pass extending the head's decode cache. Positions
                # clamp at the buffer edge: a slot that close to max_len
                # finishes before the clamped junk could be consumed.
                drafts, dlogits, tok = [], [], tokens
                for j in range(k):
                    pj = jnp.minimum(positions + j, max_pos)
                    dl, hid, mtp_kv = model.mtp_step(
                        params, 0, hid, tok, pj, mtp_kv)
                    if stochastic:
                        d = sample_tokens(dl, temperatures,
                                          jax.random.fold_in(k_draft, j),
                                          top_k=self.top_k)
                    else:
                        d = jnp.argmax(dl, axis=-1).astype(jnp.int32)
                    drafts.append(d)
                    dlogits.append(dl)
                    tok = d[:, None]

                # verify chain: k+1 decode-shaped main forwards — the
                # exact op sequence of decode_sample, repeated — feeding
                # the committed token then each draft
                mlogits, hiddens, vtok = [], [], tokens
                for j in range(k + 1):
                    pj = jnp.minimum(positions + j, max_pos)
                    lg, h, cache = model.decode_step_hidden(
                        params, cache, vtok, pj, placement=placement)
                    mlogits.append(lg)
                    hiddens.append(h)
                    if j < k:
                        vtok = drafts[j][:, None]
                ml = jnp.stack(mlogits, axis=1)

                if stochastic:
                    block, n_acc = speculative_verify(
                        ml, jnp.stack(drafts, axis=1),
                        jnp.stack(dlogits, axis=1), temperatures,
                        k_verify, top_k=self.top_k)
                else:
                    greedy = jnp.argmax(ml, axis=-1).astype(jnp.int32)
                    acc = jnp.stack(drafts, axis=1) == greedy[:, :k]
                    n_acc = jnp.cumprod(acc.astype(jnp.int32),
                                        axis=1).sum(axis=1)
                    block, n_acc = greedy, n_acc.astype(jnp.int32)

                # unconditional draft-cache fill: rewrite the head's KV
                # at positions+1..positions+k from the MAIN hiddens, so
                # accepted positions hold canonical content next
                # iteration (rejected ones hold junk that the next
                # draft/fill passes overwrite before it is attended)
                for j in range(k):
                    pj = jnp.minimum(positions + 1 + j, max_pos)
                    _, _, mtp_kv = model.mtp_step(
                        params, 0, hiddens[j], drafts[j][:, None], pj,
                        mtp_kv)
                # carry the hidden at the last ACCEPTED position — the
                # conditioning input when the next iteration drafts from
                # the residual/bonus token
                hs = jnp.concatenate(hiddens, axis=1)
                new_hid = jnp.take_along_axis(
                    hs, n_acc[:, None, None], axis=1)
                return block, n_acc, cache, {"kv": mtp_kv,
                                             "hidden": new_hid}

            self._decode_sample_mtp = jax.jit(
                _mtp_step, donate_argnums=(1, 2),
                static_argnames=("stochastic",))
            self._decode_sample_mtp_safe = jax.jit(
                _mtp_step, static_argnames=("stochastic",))

            def _reset_mtp(mtp_cache, slot):
                return jax.tree.map(lambda x: x.at[slot].set(0),
                                    mtp_cache)

            self._reset_mtp_slot = jax.jit(_reset_mtp,
                                           donate_argnums=(0,))

    def init_cache(self, max_batch: int, max_len: int) -> PyTree:
        return self.model.init_cache(max_batch, max_len)

    def prefill(self, tokens: List[int]) -> Tuple[PyTree, np.ndarray]:
        import jax.numpy as jnp

        from repro.serving.tokenizer import PAD

        n = len(tokens)
        Lp = min(_bucket_len(n), self.max_len)
        padded = list(tokens) + [PAD] * (Lp - n)
        arr = jnp.asarray(padded, jnp.int32)[None]
        mem = None if self.memory is None else self.memory[:1]
        logits, cache = self._prefill(self.params, arr, mem,
                                      jnp.asarray([n - 1], jnp.int32))
        return cache, np.asarray(logits[0], np.float32)

    @property
    def supports_chunked_prefill(self) -> bool:
        """True when every mixer attends globally (ATTN / MLA_ATTN) —
        ring-buffer windows and recurrent state caches cannot resume a
        prefill mid-prompt; those models fall back to the buffering
        default."""
        from repro.configs.base import ATTN, MLA_ATTN

        cfg = self.model.cfg
        return (not cfg.is_encdec and self.model.window_override == 0
                and all(m in (ATTN, MLA_ATTN)
                        for m, _ in cfg.layer_kinds()))

    def prefill_chunk(self, cache, tokens: List[int], offset: int,
                      total_len: int):
        """One jitted chunk program over the full-length cache buffer —
        see the module docstring for the contract. Falls back to the
        buffering default for architectures without incremental
        prefill."""
        if not self.supports_chunked_prefill:
            return super().prefill_chunk(cache, tokens, offset, total_len)
        import jax.numpy as jnp

        from repro.serving.tokenizer import PAD

        with span("backend.prefill.dispatch"):
            Lc = min(_bucket_len(max(total_len, 1)), self.max_len)
            if cache is None:
                if offset != 0:
                    raise ValueError("first chunk must start at offset 0")
                cache = self.model.init_cache(1, Lc)
            n = len(tokens)
            # pad the chunk to its bucket, clamped so the buffer write
            # stays inside the buffer (padded tail rows hold junk that the
            # next chunk overwrites / decode masks — exactly like
            # monolithic prefill's padded tail)
            Sc = min(_bucket_len(max(n, 1)), Lc - offset)
            padded = list(tokens) + [PAD] * (Sc - n)
            arr = jnp.asarray(padded, jnp.int32)[None]
            logits, cache = self._prefill_chunk(
                self.params, cache, arr, jnp.int32(offset),
                jnp.asarray([n - 1], jnp.int32))
        with span("backend.prefill.fetch"):
            logits = np.asarray(logits[0], np.float32)
        return cache, logits

    @property
    def supports_prefix_kv(self) -> bool:
        """Prefix-KV seeding rides the same incremental-prefill machinery
        as chunking (seq-addressed cache, resumable mid-prompt)."""
        return self.supports_chunked_prefill

    def slice_prefill_kv(self, cache: PyTree, tokens: List[int],
                         start: int, end: int) -> PyTree:
        """The range must be a block, ``start`` a multiple of its width:
        it comes from the batched slicer's program."""
        width = end - start
        if width <= 0 or start % width:
            raise ValueError(f"[{start}, {end}) is not a block")
        return self.slice_prefill_blocks(cache, tokens, start // width, 1,
                                         width)[0]

    def slice_prefill_blocks(self, cache: PyTree, tokens: List[int],
                             first_block: int, n_blocks: int,
                             block_size: int) -> List[PyTree]:
        """One dispatch of the module-level slicer, which materialises
        every whole block of the buffer (the span's ``n``); the blocks
        not asked for are dropped."""
        from repro.xccl.pd_transfer import slice_kv_blocks

        with span("backend.prefill.slice") as sp:
            blocks = slice_kv_blocks(cache, block_size)
            sp.n = len(blocks)
        if first_block < 0 or first_block + n_blocks > len(blocks):
            raise ValueError(
                f"blocks [{first_block}, {first_block + n_blocks}) outside "
                f"the buffer's {len(blocks)} whole blocks")
        return blocks[first_block:first_block + n_blocks]

    def seed_prefill_cache(self, payloads: List[PyTree], prefix_len: int,
                           total_len: int) -> PyTree:
        """Write the stored block payloads into a fresh full-length cache
        buffer at positions ``[0, prefix_len)``. Eager (one-shot per hit):
        the buffer then flows through the jitted chunk programs, which
        only touch positions >= offset, so the seeded region survives
        bit-exactly."""
        import jax
        from repro.xccl.pd_transfer import assemble_chunks

        Lc = min(_bucket_len(max(total_len, 1)), self.max_len)
        fresh = self.model.init_cache(1, Lc)
        kv = assemble_chunks(list(payloads))

        def one(full, part):
            return jax.lax.dynamic_update_slice(
                full, part.astype(full.dtype), (0,) * full.ndim)

        return jax.tree_util.tree_map(one, fresh, kv)

    @staticmethod
    def _write_slot_impl(cache: PyTree, cache1: PyTree, slot):
        """Jitted once per (cache1 shape bucket): a dynamic-slice insert
        at traced ``slot`` — no per-admission retrace, and the full cache
        is donated so the write is in place."""
        import jax
        import jax.numpy as jnp

        def one(path, full, one_leaf):
            keys = [getattr(p, "key", None) for p in path]
            ax = 1 if "blocks" in keys else 0
            # pad the incoming leaf up to the slot shape (cache len,
            # window…) — pad widths are static, shapes are trace-time
            target = list(full.shape)
            target[ax] = 1
            pads = [(0, t - s) for t, s in zip(target, one_leaf.shape)]
            if any(p != (0, 0) for p in pads):
                one_leaf = jnp.pad(one_leaf, pads)
            starts = [0] * full.ndim
            starts[ax] = slot
            return jax.lax.dynamic_update_slice(
                full, one_leaf.astype(full.dtype), tuple(starts))
        with jax.named_scope("write_slot"):
            return jax.tree_util.tree_map_with_path(one, cache, cache1)

    def write_slot(self, cache: PyTree, cache1: PyTree,
                   slot: int) -> PyTree:
        import jax.numpy as jnp

        return self._write_slot(cache, cache1, jnp.int32(slot))

    def apply_placement(self, table: Optional[Any]) -> None:
        """Swap the EPLB placement the jitted decode programs consume.
        Safe only between decode iterations (the caller — ``DPGroup`` —
        guarantees no donated-cache step is in flight)."""
        if table is None:
            self._placement = None
            return
        import jax.numpy as jnp

        from repro.serving.eplb import PlacementTable

        self._placement = PlacementTable(
            jnp.asarray(table.replica_slots, jnp.int32),
            jnp.asarray(table.n_replicas, jnp.int32),
            jnp.asarray(table.phys_owner, jnp.int32))

    def decode(self, cache: PyTree, tokens: np.ndarray,
               positions: np.ndarray) -> Tuple[np.ndarray, PyTree]:
        import jax.numpy as jnp

        logits, new_cache = self._decode(self.params, cache,
                                         jnp.asarray(tokens),
                                         jnp.asarray(positions),
                                         None, self._placement)
        return np.asarray(logits, np.float32), new_cache

    def decode_sample(self, cache: PyTree, tokens: np.ndarray,
                      positions: np.ndarray, temperatures: np.ndarray,
                      step: int, *, donate: bool = True
                      ) -> Tuple[Any, PyTree]:
        import jax.numpy as jnp

        with span("backend.decode.dispatch"):
            stochastic = bool(np.any(np.asarray(temperatures) > 0.0))
            fn = (self._decode_sample if donate
                  else self._decode_sample_safe)
            toks, new_cache = fn(self.params, cache, jnp.asarray(tokens),
                                 jnp.asarray(positions),
                                 jnp.asarray(temperatures, jnp.float32),
                                 self._base_key, jnp.int32(step),
                                 self._placement, stochastic=stochastic)
        return toks, new_cache

    def init_mtp_cache(self, max_batch: int, max_len: int) -> PyTree:
        return self.model.init_mtp_cache(max_batch, max_len)

    def reset_mtp_slot(self, mtp_cache: PyTree, slot: int) -> PyTree:
        import jax.numpy as jnp

        return self._reset_mtp_slot(mtp_cache, jnp.int32(slot))

    def decode_sample_mtp(self, cache: PyTree, mtp_cache: PyTree,
                          tokens: np.ndarray, positions: np.ndarray,
                          temperatures: np.ndarray, step: int, *,
                          donate: bool = True
                          ) -> Tuple[Any, Any, PyTree, PyTree]:
        import jax.numpy as jnp

        if not self.mtp_k:
            raise NotImplementedError("backend built with mtp_k=0")
        with span("backend.decode.dispatch"):
            stochastic = bool(np.any(np.asarray(temperatures) > 0.0))
            fn = (self._decode_sample_mtp if donate
                  else self._decode_sample_mtp_safe)
            block, n_acc, new_cache, new_mtp = fn(
                self.params, cache, mtp_cache, jnp.asarray(tokens),
                jnp.asarray(positions),
                jnp.asarray(temperatures, jnp.float32), self._base_key,
                jnp.int32(step), self._placement, stochastic=stochastic)
        return block, n_acc, new_cache, new_mtp

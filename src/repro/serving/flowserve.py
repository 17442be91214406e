"""FlowServe engine: DP groups + TE-shell, PD-colocated mode.

The disaggregated Prefill-Decode pipeline lives in core/pd_disagg.py; this
module is the single-TE engine used by examples and as the building block
of the disaggregated deployment (each prefill/decode TE *is* a FlowServe
engine with a role flag).
"""
from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Sequence

from repro.configs.base import ModelConfig
from repro.serving.backend import ExecutionBackend, JAXBackend
from repro.serving.dp_group import DPGroup
from repro.serving.request import Request, RequestState
from repro.serving.te_shell import TEShell
from repro.serving.tokenizer import ByteTokenizer

PyTree = Any

#: dp_id → backend; lets deployments inject non-JAX execution (the
#: SuperPod simulator's cost-model backend plugs in here).
BackendFactory = Callable[[int], ExecutionBackend]


class FlowServeEngine:
    def __init__(self, cfg: ModelConfig, params: Optional[PyTree] = None,
                 *, n_dp_groups: int = 2, max_batch: int = 4,
                 max_len: int = 256, ctx=None, seed: int = 0, memory=None,
                 backend_factory: Optional[BackendFactory] = None,
                 token_budget: int = 8192,
                 chunk_tokens: Optional[int] = None, mtp_k: int = 0):
        self.cfg = cfg
        self.model = None
        self.params = None
        if backend_factory is None:
            import jax

            from repro.launch.sharding import init_params
            from repro.models.mesh_ctx import make_smoke_ctx
            from repro.models.transformer import build_model

            self.ctx = ctx or make_smoke_ctx()
            self.model = build_model(cfg, self.ctx)
            if params is None:
                params = init_params(self.model, jax.random.PRNGKey(seed))
            self.params = params
            model = self.model

            def backend_factory(dp_id: int) -> ExecutionBackend:
                # per-group sampling seed: DP groups step in lockstep, so
                # a shared seed would draw identical Gumbel noise
                return JAXBackend(model, params, max_len=max_len,
                                  memory=memory, seed=seed * 1000 + dp_id,
                                  mtp_k=mtp_k)
        else:
            self.ctx = ctx
        self.tokenizer = ByteTokenizer()
        self.max_len = max_len
        self.dps = [
            DPGroup(i, backend_factory(i), max_batch=max_batch,
                    max_len=max_len)
            for i in range(n_dp_groups)
        ]
        from repro.serving.scheduler import PrefillScheduler
        self.shell = TEShell(
            self.dps,
            n_layers=cfg.num_layers if cfg.has_moe else 1,
            n_experts=cfg.moe.num_experts if cfg.has_moe else 0,
            prefill_scheduler=PrefillScheduler(
                n_dps=n_dp_groups, token_budget=token_budget,
                chunk_tokens=chunk_tokens))
        self.waiting: List[Request] = []
        # prefill finished but no decode slot yet: retry admission each
        # step (the pre-chunking path deferred the WHOLE prefill instead)
        self._ready: List[tuple] = []

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if req.prompt_tokens is None:
            req.prompt_tokens = self.tokenizer.encode(req.prompt)
        self.waiting.append(req)

    def submit_text(self, prompt: str, max_new_tokens: int = 32,
                    **kw) -> Request:
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens, **kw)
        self.submit(req)
        return req

    # ------------------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: schedule + run prefill CHUNKS, admit
        completed prompts, decode everywhere.

        Prefill is chunk-granular (§4.3 token-budget admission): the
        shell's ``PrefillScheduler`` emits per-DP ``ChunkWork`` slices —
        continuing partially-prefilled requests before admitting new
        ones — and each DP executes its chunks through the backend's
        ``prefill_chunk`` program. A prompt no longer than the chunk
        size behaves exactly like the old whole-prompt path.

        Decode uses the zero-sync fast path in two phases: every DP
        group's jitted decode+sample program is *launched* first (async
        dispatch — the host does not block), then the ``[B]`` int32
        token vectors are collected. Each group's device compute thereby
        overlaps the others' host-side dispatch and bookkeeping instead
        of serializing on a per-group ``[B, V]`` logits sync.
        """
        # feed new submissions to the chunk scheduler (context-clipped
        # up front so chunk boundaries are computed on the final prompt)
        for req in self.waiting:
            limit = max(self.max_len - req.max_new_tokens - 1, 16)
            if req.prompt_len > limit:
                req.prompt_tokens = req.prompt_tokens[-limit:]
            self.shell.submit_prefill(req)
        self.waiting = []
        for dp, works in zip(self.dps, self.shell.schedule_prefill_chunks()):
            for work in works:
                work.req.state = RequestState.PREFILLING
                done = dp.run_prefill_chunk(work)
                if done is not None:
                    self._ready.append((work.req, dp) + done)
        still_ready: List[tuple] = []
        for req, dp, cache1, logits in self._ready:
            if dp.can_admit(req):
                dp.admit(req, cache1, logits)
            else:
                still_ready.append((req, dp, cache1, logits))
        self._ready = still_ready
        for dp in self.dps:
            dp.decode_launch()
        produced = 0
        for dp in self.dps:
            produced += dp.decode_complete()
        return produced

    def run_eplb(self, n_npus: Optional[int] = None,
                 slots_per_npu: int = 1):
        """One EPLB pass over the shell's collected routing stats: build
        per-layer maps and install the stacked PlacementTable on every
        DP group's backend (each group swaps at its next decode-
        iteration boundary — the §4.5 live-reconfiguration contract).
        Returns the activated per-layer maps ({} when the model has no
        routed experts or nothing was collected yet)."""
        if self.shell.collector is None:
            return {}
        maps = self.shell.plan_eplb(
            n_npus or max(len(self.dps), 1), slots_per_npu)
        if maps:
            self.shell.activate_maps(maps)
        return maps

    def record_expert_counts(self, counts) -> None:
        """Feed per-layer routed token counts [n_layers, n_experts]
        (the model's ``expert_counts`` metric) into the EPLB collector."""
        self.shell.record_expert_counts(counts)

    def run_until_done(self, max_steps: int = 10_000) -> List[Request]:
        steps = 0
        while (self.waiting or self._ready
               or self.shell.prefill_sched.pending
               or any(d.active for d in self.dps)):
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine did not converge")
        for d in self.dps:
            d.drain()
        done: List[Request] = []
        for d in self.dps:
            done.extend(d.finished)
            d.finished = []
        return done

    def generate(self, prompts: Sequence[str], max_new_tokens: int = 32,
                 temperature: float = 0.0) -> List[str]:
        reqs = [self.submit_text(p, max_new_tokens,
                                 temperature=temperature) for p in prompts]
        self.run_until_done()
        by_id = {r.req_id: r for r in reqs}
        return [self.tokenizer.decode(by_id[r.req_id].output_tokens)
                for r in reqs]

    def close(self) -> None:
        for d in self.dps:
            d.close()

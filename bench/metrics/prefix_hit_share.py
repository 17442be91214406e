"""Share of prompt tokens served from the radix prefix cache
(``Request.prefix_hit_tokens``) over the requests due in the window
whose prefill had started. Layer: DP group / KV cache."""


def read(run):
    started = run.rec.first_chunk_done
    reqs = [s.req for s in run.sent
            if run.t0 <= s.due < run.t1 and s.req.req_id in started]
    total = sum(r.prompt_len for r in reqs)
    if not total:
        return None
    return 100.0 * sum(r.prefix_hit_tokens for r in reqs) / total

"""Whether what the timed path served is what the model computes.

Once the window has closed and the program's state is freed, a sample
of the requests the engine finished, drawn from the seed, holding the
longest of them and spread over the DP groups and their decode slots,
is run through the float32 reference (``reference.py``) over its
prompt and its served tokens. At every
served position the gap by which the served token's reference logit
lies below the reference's best is read; the widest gap over the sample
and its mean are compared with the configuration's limits. Served tokens are greedy,
so a sound run serves the reference's best token or one within rounding
of it.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference


@dataclasses.dataclass
class Served:
    prompt: List[int]
    tokens: List[int]
    dp: Optional[int] = None        # the DP group that decoded it
    slot: Optional[int] = None      # and its decode slot there


def finished(sent, placed: Optional[Dict[int, Tuple[int, int]]] = None
             ) -> List[Served]:
    """The client's requests (``client.Sent``) that the engine finished,
    with every token they asked for; ``placed`` maps a request id to its
    DP group and slot (``client.Recorder.placed``)."""
    placed = placed or {}
    return [Served(list(s.req.prompt_tokens), list(s.req.output_tokens),
                   *placed.get(s.req.req_id, (None, None)))
            for s in sent if s.req.state.name == "FINISHED"
            and len(s.req.output_tokens) == s.spec.max_new]


def sample(done: Sequence[Served], seed: int, n: int) -> List[Served]:
    """``n`` finished requests (all, where fewer finished): the longest,
    then others drawn from ``seed``, taken from the DP groups in turn
    and, within a group, from a slot not yet in the sample where one is
    left."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: (-len(done[i].tokens),
                                  -len(done[i].prompt)))
    by_dp: Dict[Optional[int], List[int]] = collections.defaultdict(list)
    for i in np.random.default_rng(seed).permutation(order[1:]):
        by_dp[done[i].dp].append(int(i))
    groups = sorted(by_dp, key=lambda g: -1 if g is None else g)
    picked = [order[0]]
    seen = {(done[order[0]].dp, done[order[0]].slot)}
    turn = 0
    while len(picked) < n and any(by_dp.values()):
        g = groups[turn % len(groups)]
        turn += 1
        pool = by_dp[g]
        if not pool:
            continue
        j = next((j for j, i in enumerate(pool)
                  if (g, done[i].slot) not in seen), 0)
        i = pool.pop(j)
        picked.append(i)
        seen.add((g, done[i].slot))
    return [done[i] for i in picked]


def gaps(params, conf: dict, req: Served, rounding=None) -> np.ndarray:
    """Reference gap of each served token of ``req``. With ``rounding`` the
    tokens judged are those the lower-precision control puts first at
    each served position, not the served ones (the control reads the
    same prompts and tokens)."""
    seq = list(req.prompt) + list(req.tokens[:-1])
    rows = list(range(len(req.prompt) - 1, len(seq)))
    return reference.gaps(params, conf, seq, rows, req.tokens, rounding)


def readings(params, conf: dict, reqs: Sequence[Served],
             rounding=None) -> dict:
    """The numbers a limit can hold, over every served token of
    ``reqs``: the widest gap, its 99th percentile and its mean."""
    g = np.concatenate([gaps(params, conf, r, rounding) for r in reqs])
    return {"max_gap": float(np.max(g)),
            "p99_gap": float(np.percentile(g, 99)),
            "mean_gap": float(np.mean(g)),
            "tokens": int(g.size)}

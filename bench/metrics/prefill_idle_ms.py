"""Idle device time while the program's ``dp.prefill`` span (the radix
match, the cache seed, the chunk's dispatch, the logits fetch, the radix
insert) was open, per prefill chunk that computed prompt tokens, in the
traced window. Layer: DP group and KV cache."""
from bench import spans


def read(run):
    return spans.per_span_ms(run, "dp.prefill")

"""Idle device time while the program's ``dp.decode.launch`` span (the
slot loop that gathers inputs, the decode program's dispatch) was open,
per decode launch, in the traced window. Layer: DP group and KV
cache."""
from bench import spans


def read(run):
    return spans.per_span_ms(run, "dp.decode.launch")

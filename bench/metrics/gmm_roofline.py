"""Least time the chip needs for the grouped expert FFN's required work
(``bench/roofline/gmm.py``) over the device time of its kernel events,
in the traced window. One call per MoE layer per decode step (live
slots only) and per prefill chunk (computed tokens only).
Layer: kernels."""
from bench import xplane
from bench.metrics import GMM, traced_chunks, traced_launches
from bench.roofline import gmm, least_time


def read(run):
    if run.trace is None or run.peak is None:
        return None
    conf = run.conf
    ev = xplane.ops(run.trace, GMM)
    if not len(ev):
        return None
    n_moe = conf["num_hidden_layers"] - conf.get("first_k_dense_replace", 0)
    calls = ([x.n_live for x in traced_launches(run)]
             + [c.end - c.start for c in traced_chunks(run)])
    need = sum(least_time(*gmm.work(conf, n), run.peak) for n in calls)
    if not need:
        return None
    return 100.0 * n_moe * need / ev.total_s()

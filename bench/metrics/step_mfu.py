"""Model FLOPs of every prompt token computed and every output token
decoded in the traced window, over the window times the chip's bf16
peak: the whole step's share of the peak, which bounds every kernel
roofline beside it. Layer: model."""
from bench.metrics import traced_chunks, traced_launches
from bench.roofline import decode_step


def read(run):
    if run.trace is None or run.peak is None or not run.trace.devices:
        return None
    conf = run.conf
    flops = 0.0
    for x in traced_launches(run):
        # live slots at contexts summing to ctx_sum: per-token FLOPs are
        # linear in the context, so the mean context gives the sum
        if x.n_live:
            flops += x.n_live * decode_step.flops_per_token(
                conf, x.ctx_sum / x.n_live + 1)
    head = 2.0 * conf["hidden_size"] * conf["vocab_size"]
    for c in traced_chunks(run):
        # prefill computes the output head at the chunk's last token only
        n = c.end - c.start
        mean_ctx = (c.start + c.end + 1) / 2.0
        flops += n * (decode_step.flops_per_token(conf, mean_ctx) - head)
        flops += head
    if not flops:
        return None
    peak = run.peak["bf16_flops_per_s"] * len(run.trace.devices)
    return 100.0 * flops / (run.trace.window_s * peak)

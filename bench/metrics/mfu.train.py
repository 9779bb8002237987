"""Model FLOP/s utilisation of the training window: the forward and
backward FLOPs of every token (6 N + attention, no recompute) at the
window's tokens per second, over the chip's bf16 peak."""


def read(ctx):
    c = ctx.counts
    rate = c["tokens"] / c["window_s"]
    return 100.0 * rate * c["flops_per_token"] / ctx.peak["bf16_flops_per_s"]

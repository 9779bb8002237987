"""Model FLOP/s utilisation of the serving window: forward FLOPs of every
prompt token prefilled and every token decoded in the window, attention
over each token's real context, over the chip's bf16 peak."""


def read(ctx):
    c = ctx.counts
    if not c["contexts"]:
        return None
    flops = ctx.family.forward_flops(ctx.config, c["contexts"])
    return 100.0 * flops / c["window_s"] / ctx.peak["bf16_flops_per_s"]

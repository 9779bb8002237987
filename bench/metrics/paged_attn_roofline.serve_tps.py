"""Share of its roofline that paged attention reaches in the window: the
least time of its calls (the K and V of each row's live context in exact
tokens, plus queries and outputs, at the HBM bandwidth; or the FLOPs at
the bf16 peak, whichever is longer) over their device time in the trace."""
from bench import counts


def read(ctx):
    sigs = ctx.counts["paged_signatures"]
    if ctx.trace is None or not sigs:
        return None
    n, seconds = ctx.trace.kernel_time(sigs)
    if n == 0 or seconds == 0.0:
        return None
    flops, nbytes = counts.paged_attention_cost(ctx.config,
                                                ctx.counts["paged_calls"])
    least = max(nbytes / ctx.peak["hbm_bytes_per_s"],
                flops / ctx.peak["bf16_flops_per_s"])
    return 100.0 * least / seconds

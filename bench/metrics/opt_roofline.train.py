"""Share of its roofline that the fused optimizer reaches: the least time
its calls could take (each operand read once and each result written once,
at the HBM bandwidth; the arithmetic is elementwise and never the bound)
over their device time in the trace."""
from bench import counts


def read(ctx):
    calls = ctx.counts["opt_calls"]
    if ctx.trace is None or not calls:
        return None
    least = seconds = 0.0
    for sig in {counts.signature(c[2]) for c in calls}:
        per_call = [counts.call_bytes(c) for c in calls
                    if counts.signature(c[2]) == sig]
        n, s = ctx.trace.kernel_time([sig])
        # calls sharing a signature are alike; charge the smallest of them
        least += n * min(per_call) / ctx.peak["hbm_bytes_per_s"]
        seconds += s
    if seconds == 0.0:
        return None
    return 100.0 * least / seconds

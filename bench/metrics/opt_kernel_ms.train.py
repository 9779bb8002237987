"""Device time per train step of the fused optimizer's Pallas kernels,
found in the trace by the result types that the train step's lowering
gives its optimizer kernels."""
from bench import counts


def read(ctx):
    calls = ctx.counts["opt_calls"]
    if ctx.trace is None or not calls:
        return None
    n, seconds = ctx.trace.kernel_time(counts.signature(c[2]) for c in calls)
    if n == 0:
        return None
    return 1e3 * seconds / ctx.counts["steps"]

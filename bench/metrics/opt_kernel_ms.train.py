"""Device time per train step of the fused optimizer's Pallas kernels,
found in the trace by the result types that the train step's lowering
gives its optimizer kernels."""
from bench import counts


def read(ctx):
    n, seconds = counts.kernel_time(ctx, counts.OPT_KERNELS)
    if n == 0:
        return None
    return 1e3 * seconds / ctx.counts["steps"]

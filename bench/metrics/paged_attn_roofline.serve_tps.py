"""Share of its roofline that paged attention reaches in the window: the
least time of its calls (the K and V of each row's live context in exact
tokens, plus queries and outputs, at the HBM bandwidth; or the FLOPs at
the bf16 peak, whichever is longer) over their device time in the trace."""
from bench import counts


def read(ctx):
    return counts.kernel_roofline(ctx, ["_paged_kernel"])

"""Share of its roofline that the fused optimizer reaches: the least time
its calls could take (each operand read once and each result written once,
at the HBM bandwidth; the arithmetic is elementwise and never the bound)
over their device time in the trace."""
from bench import counts


def read(ctx):
    return counts.kernel_roofline(ctx, counts.OPT_KERNELS)

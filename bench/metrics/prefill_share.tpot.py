"""Share, in %, of the paged prefill program (``jit_paged_prefill``) in the
device time of the engine's two paged programs in the traced window."""


def read(ctx):
    mods = ctx.trace.modules if ctx.trace else {}
    if "jit_paged_prefill" not in mods or "jit_paged_decode" not in mods:
        return None
    prefill, decode = mods["jit_paged_prefill"][1], mods["jit_paged_decode"][1]
    return 100.0 * prefill / (prefill + decode)

"""Share, in %, of the device's busy time in the traced window spent on the
KV page pools: self time of the ops under the ``kv_pool`` named scope, plus
that of ops with no op path whose result is a whole pool (copies that XLA
makes of a pool it cannot update in place)."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.busy_s:
        return None
    pools = set(ctx.counts.get("pool_signatures", ()))
    scoped = ctx.trace.scope_s.get("kv_pool")
    if scoped is None or not pools:
        return None
    copies = sum(s for sig, s in ctx.trace.unscoped_s.items() if sig in pools)
    return 100.0 * (scoped + copies) / ctx.trace.busy_s

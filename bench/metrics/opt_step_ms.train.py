"""Device self time per train step of the ops under the train step's
``optimizer`` named scope (the update and its application, Pallas calls and
the fusions around them)."""


def read(ctx):
    seconds = ctx.trace.scope_s.get("optimizer") if ctx.trace else None
    if not seconds:
        return None
    return 1e3 * seconds / ctx.counts["steps"]

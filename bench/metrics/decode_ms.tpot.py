"""Device milliseconds per call of the engine's paged decode program
(``jit_paged_decode``) in the traced window."""


def read(ctx):
    calls, seconds = (ctx.trace.modules.get("jit_paged_decode", (0, 0.0))
                      if ctx.trace else (0, 0.0))
    if calls == 0:
        return None
    return 1e3 * seconds / calls

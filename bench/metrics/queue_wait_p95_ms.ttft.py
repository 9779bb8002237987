"""95th percentile, over the requests due in the window, of the wait from
a request's due time to the start of the engine step that admitted it."""


def read(ctx):
    return ctx.counts.get("queue_wait_p95_ms")

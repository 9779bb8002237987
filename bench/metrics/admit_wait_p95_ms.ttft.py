"""95th percentile, over the requests due in the window, of the time each
spent queued for a slot as the program counts it (``Completion.queue_s``);
a request that failed or never finished counts as lost."""


def read(ctx):
    return ctx.counts.get("admit_wait_p95_ms")

"""Share, in %, of the rows that the window's paged decode calls computed
that held a live request: the change of the engine's ``decode_rows`` over
that of ``decode_rows_computed``."""


def read(ctx):
    moved = ctx.counts.get("program", {})
    if not moved.get("decode_rows_computed"):
        return None
    return 100.0 * moved.get("decode_rows", 0) / moved["decode_rows_computed"]

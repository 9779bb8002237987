"""Tokens the engine produced in the window, by its own counter."""


def read(ctx):
    return ctx.counts.get("program", {}).get("tokens_out")

"""Correct query answers completed in the window, per second of it."""


def read(ctx):
    return ctx.rate("query")

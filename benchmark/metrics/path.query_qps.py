"""Served path: correct query answers a second in a cell where the rate is
not judged end to end (PERF.md gives the spreads)."""


def read(ctx):
    return ctx.rate("query")

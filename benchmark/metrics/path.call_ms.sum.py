"""Executor: median wall of a Sum call (its `executor.executeSum` span)
over the traced run's profiled queries: the server's time for one, its
condition masks, filter, sum, the waits for the dispatch lock and the
fetch included."""

from harness import spans


def read(ctx):
    return spans.wall_ms(ctx, "executor.executeSum")

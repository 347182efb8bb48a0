"""Dispatch: median wall time a query waits for the process-wide dispatch
lock (`dispatch.lock_wait`, outside the `stacked.kernel` span)."""

from harness import spans


def read(ctx):
    return spans.wall_ms(ctx, "dispatch.lock_wait")

"""Dispatch: median wall time of a query's result fetch (`dispatch.fetch`:
the wait for the device and the copy to the host)."""

from harness import spans


def read(ctx):
    return spans.wall_ms(ctx, "dispatch.fetch")

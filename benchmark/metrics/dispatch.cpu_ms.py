"""Dispatch: thread CPU a query burns queueing for a batch, waiting for
the dispatch lock, launching under it, accounting for the launch and
fetching the result."""

from harness import spans


def read(ctx):
    return spans.cpu_ms(ctx, ("dispatch.queue", "dispatch.lock_wait",
                              "stacked.kernel", "dispatch.account",
                              "dispatch.fetch"))

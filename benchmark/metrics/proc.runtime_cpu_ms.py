"""Served path: CPU of the threads Python did not start (the device
runtime's and the compiler's: `/debug/vars` `threads` `runtime`) over the
window, per query request answered."""

from harness import threads


def read(ctx):
    return threads.cpu_ms(ctx, ("runtime",))

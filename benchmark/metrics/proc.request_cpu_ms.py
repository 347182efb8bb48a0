"""Served path: CPU of the threads that serve requests (the HTTP handler
threads and the work pool: `/debug/vars` `threads` `request`) over the
window, per query request answered. The edge's and the four stages'
`.cpu_ms` are parts of it."""

from harness import threads


def read(ctx):
    return threads.cpu_ms(ctx, ("request",))

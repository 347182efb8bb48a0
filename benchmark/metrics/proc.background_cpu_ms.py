"""Served path: CPU of every other named thread (cache flush, fsync
interval, watchdog, runtime monitor, the benchmark's own driver, ...:
`/debug/vars` `threads` but `request`, `runtime` and `exited`) over the
window, per query request answered. With the request and runtime parts
it makes `proc.cpu_ms_per_query` less what exited threads burned."""

from harness import threads


def read(ctx):
    return threads.cpu_ms(ctx)

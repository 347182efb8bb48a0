"""Holder: seconds of `Holder.flush_caches` inside the window
(`holder.cache_flush_seconds` counts a flush still running up to the
read, so the flush's place in the window does not matter). About a
twelfth of the saturated rate is lost while it runs."""


def read(ctx):
    return ctx.delta("vars", "holder", "cache_flush_seconds")

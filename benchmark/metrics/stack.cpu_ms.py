"""Stack cache: thread CPU a query burns in `stack.lookup` (a hit, a
patch's gather and copy, a build)."""

from harness import spans


def read(ctx):
    return spans.cpu_ms(ctx, ("stack.lookup",))

"""Stack cache: median wall time a query spends in `stack.lookup`; under
writes this is the stampede's patch copy."""

from harness import spans


def read(ctx):
    return spans.wall_ms(ctx, "stack.lookup")

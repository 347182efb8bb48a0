"""Stack cache: hits over hits and misses in the window."""


def read(ctx):
    hits = ctx.delta("vars", "stacked", "hits")
    misses = ctx.delta("vars", "stacked", "misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return hits / (hits + misses)

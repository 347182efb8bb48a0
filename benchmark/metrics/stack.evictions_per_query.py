"""Stack cache: stacks evicted in the window a query answered in it. 0
where the working set fits the budget in force; every eviction is a
rebuild (`stack.build_s`) some later query waits for."""


def read(ctx):
    evictions = ctx.delta("vars", "stacked", "evictions")
    queries = len(ctx.latencies_ms("query"))
    if evictions is None or not queries:
        return None
    return evictions / queries

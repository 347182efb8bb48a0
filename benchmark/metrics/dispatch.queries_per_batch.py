"""Dispatch: count queries per group-commit device batch."""


def read(ctx):
    queries = ctx.delta("vars", "stacked", "count_batched_queries")
    batches = ctx.delta("vars", "stacked", "count_batches")
    return queries / batches if batches else None

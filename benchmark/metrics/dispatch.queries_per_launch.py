"""Dispatch: count queries per device launch their batches made (a batch
goes out as the power-of-two chunks that add up to each of its groups; a
chunk whose program is not built yet goes out as solos). Nothing to read
on a program without the `count_launches` counter."""


def read(ctx):
    queries = ctx.delta("vars", "stacked", "count_batched_queries")
    launches = ctx.delta("vars", "stacked", "count_launches")
    return queries / launches if queries is not None and launches else None

"""Device kernels: device busy time of the traced span per query answered
in it (the mean over the chips that share the stacks)."""


def read(ctx):
    if not ctx.trace or not ctx.traced_queries:
        return None
    return ctx.trace["busy_s"] / len(ctx.traced_queries) * 1e3

"""Executor and plan: median time inside `executor.Execute` that is not
inside a `stacked.kernel` span."""

from harness import stats


def read(ctx):
    own = [(p["executor.Execute"][0] - p.get("stacked.kernel", [0.0])[0])
           * 1e3 for p in ctx.profiles if "executor.Execute" in p]
    return stats.percentile(own, 50)

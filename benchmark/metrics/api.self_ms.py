"""API layer: median self time of the `api.Query` span (its duration less
its children's: parse, translate, admission, result encoding) over the
traced run's profiled queries."""

from harness import stats


def read(ctx):
    own = [p["api.Query"][1] * 1e3 for p in ctx.profiles
           if "api.Query" in p]
    return stats.percentile(own, 50)

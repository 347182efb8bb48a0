"""What the readers of the program's stage spans share.

The program sums every live span it finishes by name into `/debug/vars`
`spans`: {name: {count, seconds, self_seconds, cpu_seconds,
self_cpu_seconds}}, `self` being a span's own less what its same-thread
children covered and `cpu` the thread-CPU clock (not wall: at 32 clients
a stage's wall is mostly the wait for the interpreter lock). A traced
run's queries carry `?profile=true`, so over the window the table gains
one tree a query. A program without the table (a parent commit), or a
cell that never opens the span, gives None.
"""

from . import stats


def cpu_ms(ctx, names=(), prefixes=(), per="api.Query"):
    """Thread CPU the spans called `names`, or starting with one of
    `prefixes`, burned themselves over the window, per span called `per`
    finished in it: milliseconds a query."""
    table = ctx.after.get("vars", {}).get("spans")
    queries = ctx.delta("vars", "spans", per, "count")
    if not table or not queries:
        return None
    picked = [name for name in table
              if name in names or name.startswith(tuple(prefixes))]
    if not picked:
        return None
    seconds = sum(ctx.delta("vars", "spans", name, "self_cpu_seconds") or 0.0
                  for name in picked)
    return seconds / queries * 1e3


def wall_ms(ctx, name):
    """Median over the profiled queries that have the span of its wall
    time a query (all spans of that name in the query's tree)."""
    return stats.percentile(
        [p[name][0] * 1e3 for p in ctx.profiles if name in p], 50)

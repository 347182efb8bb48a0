"""Served path: 95th percentile of the queries' send-to-answer time in a
cell where it is not judged end to end (PERF.md gives the spreads)."""

from harness import stats


def read(ctx):
    return stats.percentile(ctx.latencies_ms("query"), 95)

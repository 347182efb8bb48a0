"""Median send-to-answer time of all the window's queries."""

from harness import stats


def read(ctx):
    return stats.percentile(ctx.latencies_ms("query"), 50)

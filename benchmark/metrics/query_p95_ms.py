"""95th percentile of the send-to-answer time of all the window's
queries (p95, not p99: the server's 60-second cache flush puts about 0.3 %
of a window's requests behind one stall, and whether a window holds a
flush is chance)."""

from harness import stats


def read(ctx):
    return stats.percentile(ctx.latencies_ms("query"), 95)

"""Median send-to-acknowledgement time of the window's import batches."""

from harness import stats


def read(ctx):
    return stats.percentile(ctx.latencies_ms("import_bits"), 50)

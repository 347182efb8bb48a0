"""Load generator: mean time from a client's reply to its next send. A
starved generator shows here, not as a fast server."""

from harness import stats


def read(ctx):
    gaps = [r[stats.GAP] for r in ctx.records
            if r[stats.KIND] != "readback"]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None

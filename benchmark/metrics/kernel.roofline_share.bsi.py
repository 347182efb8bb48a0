"""Device kernels: the least time the chips could take for the traced
span's answered Sums (every row of each int field a query names and each
set row it filters on, read once in every shard, `harness/roofline_bsi.py`,
over the HBM peak of `device_kind` times the chips that share the stacks)
over the device's busy time there. Nothing to read without a trace or on
the host CPU."""

from harness import roofline_bsi


def read(ctx):
    if not ctx.trace or not ctx.traced_queries or not ctx.trace["busy_s"] \
            or ctx.device["platform"] != "tpu":
        return None
    least = roofline_bsi.least_seconds(
        ctx.traced_queries, ctx.config, ctx.device["kind"],
        ctx.device["count"])
    return least / ctx.trace["busy_s"] * 100

"""Device kernels: the least time the chips could take for the traced
span's answered queries (their bytes over the HBM peak of `device_kind`,
times the chips that share the stacks) over the device's busy time there.
Memory bounds it. Nothing to read without a trace or on the host CPU."""

from harness import roofline


def read(ctx):
    if not ctx.trace or not ctx.traced_queries \
            or ctx.device["platform"] != "tpu":
        return None
    least = roofline.least_seconds(ctx.traced_queries, ctx.config,
                                   ctx.device["kind"], ctx.device["count"])
    return least / ctx.trace["busy_s"] * 100

"""Stack cache: the host gather's share (fragments -> host words,
`_host_rows`) of the seconds of all cold builds since the server came up,
in per cent; the rest is container choice and upload."""


def read(ctx):
    stacked = ctx.after.get("vars", {}).get("stacked", {})
    if not stacked.get("build_seconds") \
            or "build_gather_seconds" not in stacked:
        return None
    return stacked["build_gather_seconds"] / stacked["build_seconds"] * 100

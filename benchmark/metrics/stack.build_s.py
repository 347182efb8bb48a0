"""Stack cache: seconds a cold build took (every plane of a stack gathered
from the fragments, its container chosen, the upload), the mean over all
builds since the server came up — the set-up's, where the window evicts
nothing."""


def read(ctx):
    stacked = ctx.after.get("vars", {}).get("stacked", {})
    if not stacked.get("builds") or "build_seconds" not in stacked:
        return None
    return stacked["build_seconds"] / stacked["builds"]

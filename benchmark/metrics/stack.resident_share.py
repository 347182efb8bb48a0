"""Stack cache: the leaf/BSI pool's bytes over the budget in force
(`stacked.stack_budget_bytes`, a share of the device's memory) at the
window's close, in per cent. Nothing to read from a program that does
not say what its budget is."""


def read(ctx):
    stacked = ctx.after.get("vars", {}).get("stacked", {})
    budget = stacked.get("stack_budget_bytes")
    if not budget or "stack_bytes" not in stacked:
        return None
    return stacked["stack_bytes"] / budget * 100

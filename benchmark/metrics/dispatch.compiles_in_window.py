"""Dispatch: programs that compiled inside the window (expected 0), from
the dispatch phase clock's `compile` marks."""


def read(ctx):
    phases = ctx.after.get("dispatch", {}).get("phases")
    if phases is None:
        return None
    return sum(ctx.delta("dispatch", "phases", kernel, "compile", "count")
               or 0 for kernel in phases)

"""Executor and plan: thread CPU a query burns in `executor.Execute`, the
per-call `executor.execute<Call>` spans and `exec.plan` themselves: what
is left of the executor once stack lookups and dispatch are taken out."""

from harness import spans


def read(ctx):
    return spans.cpu_ms(ctx, ("executor.Execute", "exec.plan"),
                        prefixes=("executor.execute",))

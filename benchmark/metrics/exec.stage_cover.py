"""Executor and plan: how much of `executor.Execute`'s wall its stage
spans account for (`exec.plan`, `stack.lookup`, `dispatch.queue`,
`dispatch.lock_wait`, `stacked.kernel`, `dispatch.account`,
`dispatch.fetch`), median over
the profiled queries, in per cent. What is missing is the executor's own
glue between the stages; a fall means a stage nobody has named yet."""

from harness import stats

STAGES = ("exec.plan", "stack.lookup", "dispatch.queue",
          "dispatch.lock_wait", "stacked.kernel", "dispatch.account",
          "dispatch.fetch")


def read(ctx):
    shares = [sum(p[s][0] for s in STAGES if s in p)
              / p["executor.Execute"][0] * 100
              for p in ctx.profiles
              if p.get("executor.Execute", [0])[0] > 0 and "exec.plan" in p]
    return stats.percentile(shares, 50)

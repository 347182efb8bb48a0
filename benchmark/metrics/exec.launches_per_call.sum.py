"""Executor: the dispatch-lock holds a Sum answered on the stacked path
took in the window (`/debug/vars` `stacked` `sum_dispatches` ÷
`sum_calls`): its range conditions' masks, its filter and the sum.
Nothing to read from a program without the `sum_calls` counter, or from
a window without a Sum."""


def read(ctx):
    launches = ctx.delta("vars", "stacked", "sum_dispatches")
    calls = ctx.delta("vars", "stacked", "sum_calls")
    if launches is None or not calls:
        return None
    return launches / calls

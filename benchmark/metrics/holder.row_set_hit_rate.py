"""Holder: the share of `Rows()` and TopN candidate sets that a view
answered from its kept union in the window (`/debug/vars` `holder`
`row_set_hits` ÷ (`row_set_hits` + `row_set_walks`)). Nothing to read
from a program without the counters."""


def read(ctx):
    hits = ctx.delta("vars", "holder", "row_set_hits")
    walks = ctx.delta("vars", "holder", "row_set_walks")
    if hits is None or walks is None or not hits + walks:
        return None
    return hits / (hits + walks)

"""Executor: device dispatches per served query request."""

ROUTE = "/index/(?P<index>[^/]+)/query"


def read(ctx):
    _, queries = ctx.timing("http_request_seconds", route=ROUTE,
                            status="200")
    dispatches = ctx.delta("vars", "stacked", "dispatches")
    return dispatches / queries if queries and dispatches is not None \
        else None

"""HTTP edge: mean server-side time of a query request, from the
`http_request_seconds` histogram of the query route over the window."""

ROUTE = "/index/(?P<index>[^/]+)/query"


def read(ctx):
    seconds, count = ctx.timing("http_request_seconds", route=ROUTE,
                                status="200")
    return seconds / count * 1e3 if count else None

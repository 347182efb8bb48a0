"""HTTP edge: mean server-side time of an import request."""

ROUTE = "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import"


def read(ctx):
    seconds, count = ctx.timing("http_request_seconds", route=ROUTE,
                                status="200")
    return seconds / count * 1e3 if count else None

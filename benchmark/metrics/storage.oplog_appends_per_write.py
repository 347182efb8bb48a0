"""Storage: oplog appends per acknowledged import (expected 1.0)."""

ROUTE = "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import"


def read(ctx):
    _, writes = ctx.timing("http_request_seconds", route=ROUTE,
                           status="200")
    appends = ctx.delta("oplog", "appends")
    return appends / writes if writes and appends is not None else None

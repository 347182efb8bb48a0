"""Stack cache: planes re-uploaded to the device per acknowledged import."""

ROUTE = "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import"


def read(ctx):
    _, writes = ctx.timing("http_request_seconds", route=ROUTE,
                           status="200")
    planes = ctx.delta("vars", "stacked", "planes_uploaded")
    return planes / writes if writes and planes is not None else None

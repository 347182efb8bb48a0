"""Served path: all host CPU of the server process over the window
(`process.cpu_seconds` = `time.process_time()`: every thread, the
runtime's and the edge's included) per query request answered. Less the
four stage `*.cpu_ms` it is the edge, the runtime's threads and whatever
runs outside any span."""

ROUTE = "/index/(?P<index>[^/]+)/query"


def read(ctx):
    cpu = ctx.delta("vars", "process", "cpu_seconds")
    _, queries = ctx.timing("http_request_seconds", route=ROUTE)
    return cpu / queries * 1e3 if cpu is not None and queries else None

"""HTTP edge: thread CPU a profiled query request burns at the edge: its
edge span `http.POST <query route>` less the `query` profile under it,
that is the edge's own CPU and that of its children `http.parse` (headers
and body read) and `http.reply` (encoding, the write), per `api.Query`.
A program whose profiled requests open no edge span gives None."""

EDGE = "http.POST /index/(?P<index>[^/]+)/query"


def read(ctx):
    edge = ctx.delta("vars", "spans", EDGE, "cpu_seconds")
    query = ctx.delta("vars", "spans", "query", "cpu_seconds")
    queries = ctx.delta("vars", "spans", "api.Query", "count")
    if edge is None or query is None or not queries:
        return None
    return (edge - query) / queries * 1e3

"""Storage: thread CPU of one import request, its edge span `http.POST
<import route>` with every stage under it (`http.parse`, `oplog.append`,
`field.import`, `oplog.applied`, `http.reply`, the JSON decode in the
edge's own), mean over the window's imports. A program that opens no span
on an import gives None."""

EDGE = "http.POST /index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import"


def read(ctx):
    cpu = ctx.delta("vars", "spans", EDGE, "cpu_seconds")
    imports = ctx.delta("vars", "spans", EDGE, "count")
    return cpu / imports * 1e3 if cpu is not None and imports else None

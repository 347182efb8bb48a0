"""API and admission: thread CPU a query burns in `api.Query` itself, in
`pql.parse` and in `exec.translate` (keys in, keys out)."""

from harness import spans


def read(ctx):
    return spans.cpu_ms(ctx, ("api.Query", "pql.parse", "exec.translate"))

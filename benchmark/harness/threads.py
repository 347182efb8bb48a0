"""What the readers of the program's CPU by thread group share.

`/debug/vars` `threads` is {group: {cpu_seconds, threads}}: `request`
(HTTP handler threads and the work pool), `runtime` (threads Python did
not start), `exited` (process CPU less the live threads'), and one group
per other named thread. The readers put a window's CPU seconds over the
queries answered in it, as `proc.cpu_ms_per_query` does. A program
without the section (a parent commit) gives None.
"""

ROUTE = "/index/(?P<index>[^/]+)/query"
OWN = ("request", "runtime", "exited")


def cpu_ms(ctx, groups=None):
    """CPU milliseconds the thread groups `groups` (None: every named
    group but request, runtime and exited) burned over the window, per
    query request answered."""
    table = ctx.after.get("vars", {}).get("threads")
    _, queries = ctx.timing("http_request_seconds", route=ROUTE)
    if not table or not queries:
        return None
    if groups is None:
        groups = [g for g in table if g not in OWN]
    seconds = sum(ctx.delta("vars", "threads", g, "cpu_seconds") or 0.0
                  for g in groups)
    return seconds / queries * 1e3

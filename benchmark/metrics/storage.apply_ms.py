"""Storage: mean wall of `field.import` over the window's imports, the
apply of an import to the host fragments (their `add_many`, the rows'
invalidation, existence) between `oplog.append` and `oplog.applied`. A
program that opens no span on an import gives None."""


def read(ctx):
    seconds = ctx.delta("vars", "spans", "field.import", "seconds")
    imports = ctx.delta("vars", "spans", "field.import", "count")
    return seconds / imports * 1e3 if seconds is not None and imports \
        else None

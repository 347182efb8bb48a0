"""The bytes a filtered Sum has to read, from the query and the
configuration: the work, not the implementation.

A Sum of an int field under an Intersect of set rows and range conditions
on int fields has to read, in every shard, every row of the measure's BSI
view and of each condition field's (bit depth + the existence and sign
rows, D + 2 each: a range compare and a signed sum read all of them) and
each set row once: rows x shards x words-per-row x 4 bytes. The compares
and popcounts are a few integer operations a word, so memory bounds the
least time (`harness/roofline.py` says the same of a Count).
"""

from . import peaks, ssb_reference


def query_bytes(pql, config):
    measure, conditions = ssb_reference.parse(pql)
    ints = config["int_fields"]
    int_fields = {measure} | {f for f, _, _ in conditions if f in ints}
    set_rows = sum(1 for f, _, _ in conditions if f not in ints)
    rows = sum(ints[f]["bit_depth"] + 2 for f in int_fields) + set_rows
    words = config["shard_width"] // 32
    return rows * config["shards"] * words * 4


def least_seconds(pqls, config, device_kind, chips):
    """The least time `chips` chips sharing the stacks could take to
    answer `pqls`: their bytes over the summed HBM peak."""
    total = sum(query_bytes(p, config) for p in pqls)
    return total / (peaks.hbm_bytes_per_s(device_kind) * chips)

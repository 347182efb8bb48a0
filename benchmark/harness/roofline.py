"""The bytes a query has to read, from the query and the configuration:
the work, not the implementation. A Pallas kernel, a fused plan or a
batched program that answers the same queries is held to the same bytes.

A `Count` over set algebra has to read every leaf row's plane in every
shard once: leaves x shards x words-per-row x 4 bytes. The popcount itself
is a few integer operations per word, far under the chip's integer rate,
so memory bounds the least time.
"""

from . import oracle, peaks


def query_bytes(pql, config):
    words = config["shard_width"] // 32
    return len(oracle.leaves(oracle.parse(pql))) * config["shards"] * words * 4


def least_seconds(pqls, config, device_kind, chips):
    """The least time `chips` chips sharing the stacks could take to answer
    `pqls`: their bytes over the summed HBM peak."""
    total = sum(query_bytes(p, config) for p in pqls)
    return total / (peaks.hbm_bytes_per_s(device_kind) * chips)

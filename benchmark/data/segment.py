"""Data of the `segment-*` configurations: a synthetic set index.

`shards` x 2^20 columns; every (field, row) plane of every shard is drawn
independently from the seed, `word_density` of its 32-bit words non-zero
(random bits), so each leaf stack on the device is dense. The sizes come
from the configuration's JSON file, never from here.

Three entry points, all JAX-free (they run in helper processes while the
server's process holds the chip):

    shard_planes(cfg, seed, shard) -> {(field, row): uint32[words]}
    load(cfg, seed, url)      sends the data over HTTP (import_roaring)
    expected(cfg, seed, pqls) the numpy oracle's answer to each query
"""

import multiprocessing
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (REPO, os.path.join(REPO, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CONTAINER_BITS = 1 << 16
BUILDERS = 4       # blob-building processes running ahead of the sender
CHUNK = 16         # blobs per task handed to a builder


def words_per_row(cfg):
    return cfg["shard_width"] // 32


def shard_planes(cfg, seed, shard):
    """One shard's planes. Each shard has its own stream of the seed, so
    helpers can make any range of shards without making the rest."""
    rng = np.random.default_rng([int(seed), 1, int(shard)])
    n = words_per_row(cfg)
    out = {}
    for field in cfg["fields"]:
        for row in cfg["rows"]:
            words = rng.integers(0, 1 << 32, n, dtype=np.uint32)
            keep = rng.random(n, dtype=np.float32) < cfg["word_density"]
            out[field, row] = np.where(keep, words, np.uint32(0))
    return out


def _blobs(task):
    """Builder process: [(field, shard, roaring bytes, bits)] for a run of
    shards. `optimize=False` keeps array containers, which the server
    merges several times faster than run containers of the same bits."""
    from pilosa_tpu.roaring import Bitmap, serialize

    cfg, seed, shards = task
    per_row = cfg["shard_width"] // CONTAINER_BITS
    out = []
    for shard in shards:
        planes = shard_planes(cfg, seed, shard)
        for field in cfg["fields"]:
            bitmap = Bitmap()
            bits = 0
            for row in cfg["rows"]:
                bitmap.replace_dense_words(
                    row * per_row, per_row, planes[field, row])
                bits += int(np.bitwise_count(planes[field, row]).sum())
            out.append((field, shard, serialize(bitmap, optimize=False),
                        bits))
    return out


def load(cfg, seed, url):
    """Create the index and send every fragment, one request after another
    (concurrent imports only contend in the server) while a pool of
    builders stays ahead of the sender. Returns what was acknowledged."""
    from pilosa_tpu.server.client import Client

    client = Client(url, timeout=600, retries=0)
    index = cfg["index"]
    client.create_index(index)
    for field in cfg["fields"]:
        client.create_field(index, field)
    shards = list(range(cfg["shards"]))
    tasks = [(cfg, seed, shards[i:i + CHUNK])
             for i in range(0, len(shards), CHUNK)]
    changed = want = requests = 0
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(BUILDERS, len(tasks))) as pool:
        for blobs in pool.imap(_blobs, tasks):
            for field, shard, blob, bits in blobs:
                changed += client.import_roaring(
                    index, field, shard, blob)["changed"]
                want += bits
                requests += 1
    return {"requests": requests, "bits_acknowledged": changed,
            "bits_sent": want}


def expected(cfg, seed, pqls):
    """{pql: count} by the numpy oracle, a block of shards at a time."""
    from harness import oracle

    trees = {pql: oracle.parse_count(pql) for pql in pqls}
    totals = dict.fromkeys(trees, 0)
    for shard in range(cfg["shards"]):
        planes = shard_planes(cfg, seed, shard)
        for pql, tree in trees.items():
            totals[pql] += oracle.popcount(oracle.plane(tree, planes))
    return totals

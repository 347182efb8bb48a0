"""Data of the `ssb-*` configurations: the Star Schema Benchmark's fact
table `lineorder`, one column a row.

`rows` rows over `shards` x 2^20 columns (the last shard partly filled).
Every row draws, independently from the seed, dbgen's distributions as the
configuration's file states them: an order date (a day of `orderdate`), a
discount, a quantity and a part key; the measure is the part's TPC-H retail
price x quantity x discount, in cents. The date dimension's attributes
(`date_table`) are carried on the fact row as mutex set fields, the
discount, the quantity and the measure as int fields. Sizes, ranges and
bit depths come from the configuration's JSON file, never from here.

Three entry points, all JAX-free (they run in helper processes while the
server's process holds the chip):

    shard_columns(cfg, seed, shard) -> {column: values of the shard's rows}
    load(cfg, seed, url)      sends the data over HTTP (import_roaring)
    expected(cfg, seed, pqls) the plain reference's answer to each query

An int field is sent as `import_roaring` blobs into its `bsig_<field>`
view, laid out as `Field.import_values` lays a value out: row 0 the
existence bit, row 1 the sign, row 2 + i bit i of |value - base|.
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
BSI_EXISTS, BSI_SIGN, BSI_OFFSET = 0, 1, 2
BUILDERS = 4       # blob-building processes running ahead of the sender
CHUNK = 2          # shards per task handed to a builder


def rows_in(cfg, shard):
    width = cfg["shard_width"]
    return max(0, min(width, cfg["rows"] - shard * width))


def date_table(cfg):
    """The date dimension over `orderdate`'s days: {attribute: value of
    each day}, a day being its index from the first."""
    span = cfg["orderdate"]
    days = np.arange(np.datetime64(span["first"]),
                     np.datetime64(span["last"]) + 1)
    years = days.astype("datetime64[Y]")
    year = years.astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    day_of_year = (days - years).astype(np.int64) + 1
    return {"year": year, "yearmonth": year * 100 + month,
            "weeknum": (day_of_year - 1) // 7 + 1}


def retail_price_cents(partkey):
    """TPC-H's P_RETAILPRICE of a part key, in cents (TPC-H 4.2.3)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def shard_columns(cfg, seed, shard):
    """The values every row of one shard has: `orderdate` (a day index),
    and each int field's value. Each (column, shard) has its own stream
    of the seed, so helpers can make any shard without making the rest."""
    n = rows_in(cfg, shard)

    def rng(k):
        return np.random.default_rng([int(seed), 3, k, int(shard)])

    ints = cfg["int_fields"]
    days = len(date_table(cfg)["year"])
    disc, qty = ints["lo_discount"], ints["lo_quantity"]
    out = {
        "orderdate": rng(0).integers(0, days, n).astype(np.uint16),
        "lo_discount": rng(1).integers(disc["min"], disc["max"] + 1, n),
        "lo_quantity": rng(2).integers(qty["min"], qty["max"] + 1, n),
    }
    partkey = rng(3).integers(1, cfg["parts"] + 1, n)
    out["lo_disc_revenue"] = (out["lo_quantity"]
                              * retail_price_cents(partkey)
                              * out["lo_discount"])
    return out


def _plane(hit, width):
    """A row's 2^20-bit plane as uint32 words from a bool a row."""
    full = np.zeros(width, dtype=bool)
    full[:len(hit)] = hit
    return np.packbits(full, bitorder="little").view("<u4")


def _blobs(task):
    """Builder process: [(field, view, shard, roaring bytes, bits)] for a
    run of shards. A set field's row plane is set wherever a row has that
    value; an int field's blob holds its BSI view's rows. Array containers
    kept (`optimize=False`), as `segment.py` sends them."""
    from pilosa_tpu.roaring import Bitmap, serialize

    cfg, seed, shards = task
    width = cfg["shard_width"]
    per_row = width // CONTAINER_BITS
    dates = date_table(cfg)
    out = []
    for shard in shards:
        columns = shard_columns(cfg, seed, shard)
        for field, attribute in sorted(cfg["set_fields"].items()):
            values = dates[attribute][columns["orderdate"]]
            bitmap = Bitmap()
            for row in np.unique(values):
                bitmap.replace_dense_words(
                    int(row) * per_row, per_row, _plane(values == row, width))
            out.append((field, "standard", shard,
                        serialize(bitmap, optimize=False), len(values)))
        for field, spec in sorted(cfg["int_fields"].items()):
            stored = columns[field] - spec["base"]
            magnitude = np.abs(stored)
            planes = [(BSI_EXISTS, np.ones(len(stored), dtype=bool)),
                      (BSI_SIGN, stored < 0)]
            planes += [(BSI_OFFSET + i, (magnitude >> i) & 1 == 1)
                       for i in range(spec["bit_depth"])]
            bitmap = Bitmap()
            bits = 0
            for row, hit in planes:
                bits += int(np.count_nonzero(hit))
                bitmap.replace_dense_words(row * per_row, per_row,
                                           _plane(hit, width))
            out.append((field, "bsig_" + field, shard,
                        serialize(bitmap, optimize=False), bits))
    return out


def create_schema(client, cfg):
    """The index, its mutex set fields and its int fields; the server has
    to derive the base and bit depth the loader lays values out with."""
    index = cfg["index"]
    client.create_index(index)
    for field in sorted(cfg["set_fields"]):
        client.create_field(index, field, cfg["set_field_options"])
    for field, spec in sorted(cfg["int_fields"].items()):
        client.create_field(index, field, {"type": "int", "min": spec["min"],
                                           "max": spec["max"]})
    schema = client.schema()
    for ix in schema["indexes"]:
        if ix["name"] != index:
            continue
        for f in ix["fields"]:
            spec = cfg["int_fields"].get(f["name"])
            if spec is not None and (
                    f["options"]["base"], f["options"]["bitDepth"]) != (
                    spec["base"], spec["bit_depth"]):
                raise RuntimeError(
                    f"{f['name']}: the server lays values out with base "
                    f"{f['options']['base']} and bit depth "
                    f"{f['options']['bitDepth']}, the configuration says "
                    f"{spec['base']} and {spec['bit_depth']}")


def load(cfg, seed, url):
    """Create the schema and send every fragment, one request after
    another while a pool of builders stays ahead of the sender. Returns
    what was acknowledged."""
    from pilosa_tpu.server.client import Client

    client = Client(url, timeout=600, retries=0)
    create_schema(client, cfg)
    index = cfg["index"]
    shards = list(range(cfg["shards"]))
    tasks = [(cfg, seed, shards[i:i + CHUNK])
             for i in range(0, len(shards), CHUNK)]
    changed = want = requests = 0
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(BUILDERS, len(tasks))) as pool:
        for blobs in pool.imap(_blobs, tasks):
            for field, view, shard, blob, bits in blobs:
                changed += client.import_roaring(
                    index, field, shard, blob, view=view)["changed"]
                want += bits
                requests += 1
    return {"requests": requests, "bits_acknowledged": changed,
            "bits_sent": want}


def expected(cfg, seed, pqls):
    """{pql: the JSON a correct server answers}, by the plain reference
    over every shard's value columns."""
    from harness import ssb_reference

    queries = {pql: ssb_reference.parse(pql) for pql in pqls}
    table = ssb_reference.Table(cfg, date_table(cfg))
    for shard in range(cfg["shards"]):
        table.tally(shard_columns(cfg, seed, shard))
    return {pql: table.answer(q) for pql, q in queries.items()}

"""`seg1b-rw-c32`'s shape at 8 shards on the host CPU, over HTTP (ISSUE
29): the benchmark's configuration and traffic files, every fragment
loaded as its loader loads it, each client's writes drawn as its
generator draws them — 64 pairs into row 100+k of the client's own
shard, acknowledged, read back at once with `shards=` — with two-leaf
Counts over rows 1 and 2 between the writes, every answer against the
benchmark's numpy oracle. What `/debug/vars` `stacked` has to say: a
write stales no stack that a reader asks for."""

import json
import os
import sys
import types
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, TRAFFIC = "segment-1b", "rw95-c32"
SEED = 2147491029          # past 2**31, as the driver's seeds are
SHARDS = 8
CLIENTS = 6
WAIT = 120


@pytest.fixture(scope="module")
def bench():
    for path in (BENCH, os.path.join(BENCH, "data")):
        sys.path.insert(0, path)
    try:
        import segment
        from harness import manifest, traffic

        config = manifest.config(manifest.load(), CONFIG)
        config["shards"] = SHARDS
        yield types.SimpleNamespace(
            segment=segment, traffic=traffic, config=config,
            spec=traffic.load(manifest.traffic_path(TRAFFIC)))
    finally:
        for path in (BENCH, os.path.join(BENCH, "data")):
            sys.path.remove(path)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    from tests.harness import ServerHarness, load_segment_index

    cfg = bench.config
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("rw")))
    try:
        sent, acknowledged = load_segment_index(h, bench.segment, cfg, SEED)
        assert sent == acknowledged > 0
        h.index = cfg["index"]
        h.expected = bench.segment.expected(
            cfg, SEED, bench.traffic.distinct_queries(bench.spec))
        yield h
    finally:
        h.close()


def _send(h, path, body, content_type):
    req = urllib.request.Request(h.address + path, data=body, method="POST",
                                 headers={"Content-Type": content_type})
    with urllib.request.urlopen(req, timeout=WAIT) as r:
        assert r.status == 200
        return json.loads(r.read())


def _stacked(h):
    with urllib.request.urlopen(h.address + "/debug/vars",
                                timeout=WAIT) as r:
        return json.loads(r.read())["stacked"]


def test_a_write_and_its_read_back_stale_no_stack_a_reader_asks_for(
        bench, served):
    requests = bench.traffic.requests
    read, write = bench.spec["operations"]
    assert (read["kind"], write["kind"]) == ("query", "import_bits")
    assert write["pairs"] == 64 and write["row"] == {"client": {"base": 100}}
    # warm, as the cell does: every distinct query once
    assert len(served.expected) == 16
    for pql, want in sorted(served.expected.items()):
        got = _send(served, *requests({"kind": "query", "pql": pql},
                                      served.index)[0])
        assert got["results"][0] == want, pql
    assert _stacked(served)["stack_entries"] == 4

    draws = [bench.traffic.ClientDraw(bench.spec, bench.config, SEED, k)
             for k in range(CLIENTS)]
    acked = {}
    writes = counts = 0
    start = _stacked(served)
    for turn in range(3):
        for k, draw in enumerate(draws):
            before = _stacked(served)
            op = draw.fill(write)
            assert op["row"] == 100 + k
            assert {c // bench.config["shard_width"]
                    for c in op["columns"]} == {op["shard"]} == {draw.shard}
            have = acked.setdefault((op["field"], op["row"]), set())
            importing, reading_back = requests(op, served.index)
            assert f"shards={op['shard']}" in reading_back[0]
            _send(served, *importing)
            have.update(op["columns"])
            # acknowledged => readable at once, exactly
            assert _send(served, *reading_back)["results"][0] == len(have)
            writes += 1
            for _ in range(4):
                query = draw.fill(read)
                got = _send(served, *requests(query, served.index)[0])
                assert got["results"][0] == served.expected[query["pql"]]
                counts += 1
            after = _stacked(served)
            # the four Counts: two leaves each, every lookup a hit on the
            # stamp; nothing walked, gathered, patched or built
            assert after["hits"] - before["hits"] == 8
            for name in ("misses", "patches", "builds", "evictions"):
                assert after[name] == before[name], (name, turn, k)
            # the read-back names one shard: under MIN_SHARDS it reads
            # the fragment's own plane and builds no stack at all (ISSUE
            # 29 expected one plane a write; it is none)
            assert after["planes_uploaded"] == before["planes_uploaded"]
    end = _stacked(served)
    assert writes == 3 * CLIENTS and counts == 4 * writes
    assert end["patches"] == start["patches"]
    assert end["planes_uploaded"] == start["planes_uploaded"]
    assert end["stack_entries"] == start["stack_entries"] == 4
    # at rest: every distinct answer still the oracle's
    for pql, want in sorted(served.expected.items()):
        got = _send(served, *requests({"kind": "query", "pql": pql},
                                      served.index)[0])
        assert got["results"][0] == want, pql


def test_a_write_into_a_row_that_is_read_patches_one_plane(bench, served):
    """What this PR does not cure: a write into a cached row still takes
    the patch branch, one plane a stale stack — and stays exact."""
    cfg = bench.config
    width = cfg["shard_width"]
    pql = "Count(Union(Row(f=1), Row(g=1)))"
    planes = bench.segment.shard_planes(cfg, SEED, 5)
    held = np.flatnonzero(np.unpackbits(
        (planes["f", 1] | planes["g", 1]).view(np.uint8),
        bitorder="little"))
    free = sorted(set(range(4096)) - set(held[held < 4096].tolist()))[:64]
    assert len(free) == 64
    before = _stacked(served)
    assert _send(served, f"/index/{served.index}/query", pql.encode(),
                 "text/plain")["results"][0] == served.expected[pql]
    body = json.dumps({"rowIDs": [1] * 64,
                       "columnIDs": [5 * width + c for c in free]}).encode()
    _send(served, f"/index/{served.index}/field/f/import", body,
          "application/json")
    assert _send(served, f"/index/{served.index}/query", pql.encode(),
                 "text/plain")["results"][0] == served.expected[pql] + 64
    after = _stacked(served)
    assert after["patches"] - before["patches"] == 1
    assert after["planes_uploaded"] - before["planes_uploaded"] == 1

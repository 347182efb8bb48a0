"""`segment-1b-r8` at a small size on the host CPU (ISSUE 28): the
benchmark's configuration and traffic files at 8 shards, loaded through
`import_roaring`, asked over HTTP, every answer against the benchmark's
numpy oracle (which imports nothing of the program) — with the stack
budget as the module has it, and with one 1.9 times smaller than the
working set; where the budgets come from; the build path's spans and
counters; the four metric readers."""

import json
import os
import sys
import threading
import types
import urllib.request

import pytest

from pilosa_tpu.exec import stacked
from pilosa_tpu.utils import device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, TRAFFIC, CELL = "segment-1b-r8", "read-zipf4-c32", "seg1b-r8-read-c32"
SEED = 2147491028          # past 2**31, as the driver's seeds are
SHARDS = 8
OPERATORS = ("Intersect", "Union", "Difference", "Xor")
MIB = 1 << 20
WAIT = 120


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported as its files import them,
    with the new configuration at 8 shards and the new traffic file."""
    for path in (BENCH, os.path.join(BENCH, "data")):
        sys.path.insert(0, path)
    try:
        import segment
        from harness import cell, manifest, traffic

        listed = manifest.load()
        config = manifest.config(listed, CONFIG)
        config["shards"] = SHARDS
        yield types.SimpleNamespace(
            segment=segment, cell=cell, manifest=manifest, traffic=traffic,
            listed=listed, config=config,
            spec=traffic.load(manifest.traffic_path(TRAFFIC)))
    finally:
        for path in (BENCH, os.path.join(BENCH, "data")):
            sys.path.remove(path)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """One in-process server holding the 8-shard index, every fragment
    sent as the benchmark's loader sends it (a roaring blob a field and
    shard, array containers), and the oracle's answer to each of the 64
    distinct queries."""
    from tests.harness import ServerHarness, load_segment_index

    cfg = bench.config
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("r8")))
    try:
        sent, acknowledged = load_segment_index(h, bench.segment, cfg, SEED)
        assert sent == acknowledged > 0
        pqls = bench.traffic.distinct_queries(bench.spec)
        h.expected = bench.segment.expected(cfg, SEED, pqls)
        h.index = cfg["index"]
        yield h
    finally:
        h.close()


def _ask(h, pql, profile=False):
    url = f"{h.address}/index/{h.index}/query" + (
        "?profile=true" if profile else "")
    req = urllib.request.Request(url, data=pql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=WAIT) as r:
        return json.loads(r.read())


def _get(h, path):
    with urllib.request.urlopen(h.address + path, timeout=WAIT) as r:
        return json.loads(r.read())


def _working_set(h):
    """Bytes of the eight leaf stacks: shards padded to the devices they
    are sharded over (conftest gives the host eight)."""
    ev = h.api.executor._stacked
    return 8 * ev._padded_len(range(SHARDS)) * (1 << 20) // 8


@pytest.fixture
def small_budget(served, monkeypatch):
    """The parent's situation in miniature: a stack budget 1.9 times
    smaller than the working set, through the seam the other tests use
    (the constant that stands where the backend reports no memory)."""
    budget = int(_working_set(served) / 1.9)
    monkeypatch.setattr(stacked, "MAX_STACK_BYTES", budget)
    served.api.executor._stacked.invalidate()
    return budget


# ------------------------------------------------------------- the answers


def test_the_files_say_what_the_cell_is(bench):
    assert len(bench.traffic.distinct_queries(bench.spec)) == 64
    assert bench.config["rows"] == [1, 2, 3, 4]
    assert bench.config["fields"] == ["f", "g"]
    args = bench.spec["operations"][0]["args"]
    assert args["i"] == args["j"] == {"zipf": {"n": 4, "s": 0.99, "base": 1}}
    full = bench.manifest.config(bench.listed, CONFIG)
    assert 8 * full["shards"] * full["shard_width"] // 8 == 1_000_341_504
    assert full["reduced"] == []


@pytest.mark.parametrize("op", OPERATORS)
def test_every_distinct_query_equals_the_oracle(served, op):
    asked = 0
    for pql, want in sorted(served.expected.items()):
        if pql.startswith(f"Count({op}("):
            assert _ask(served, pql)["results"][0] == want, pql
            asked += 1
    assert asked == 16
    if op == "Xor":     # the last of the four: everything is resident
        stats = _get(served, "/debug/vars")["stacked"]
        assert stats["stack_entries"] >= 8
        assert stats["stack_bytes"] >= _working_set(served)
        assert stats["stack_bytes"] <= stats["stack_budget_bytes"]


def test_a_budget_under_the_working_set_evicts_and_still_answers(
        served, small_budget):
    before = _get(served, "/debug/vars")["stacked"]
    assert before["stack_budget_bytes"] == small_budget
    for pql, want in sorted(served.expected.items()):
        assert _ask(served, pql)["results"][0] == want, pql
        stats = _get(served, "/debug/vars")["stacked"]
        hbm = _get(served, "/debug/hbm")
        assert stats["stack_bytes"] <= small_budget
        assert hbm["total_bytes"] == hbm["stack_bytes"] + hbm[
            "rows_stack_bytes"] == sum(
                e["bytes"] for e in hbm["by_index_field"])
        assert hbm["stack_budget_bytes"] == small_budget
    after = _get(served, "/debug/vars")["stacked"]
    assert after["evictions"] > before["evictions"]
    assert after["evictions_by_cause"]["stack.budget"] > 0
    assert after["builds"] - before["builds"] > 8      # stacks came back
    assert after["stack_entries"] == 4                 # 4 of 8 fit


def test_32_clients_under_a_small_budget_are_answered_exactly(
        bench, served, small_budget):
    """Evict-while-in-use: a batch holds stacks that the pool has
    dropped, and callers that miss together all rebuild."""
    wrong, errors = [], []

    def client(number):
        draw = bench.traffic.ClientDraw(bench.spec, bench.config, SEED,
                                        number)
        try:
            for _ in range(24):
                pql = draw.draw()["pql"]
                got = _ask(served, pql)["results"][0]
                if got != served.expected[pql]:
                    wrong.append((pql, got))
        except Exception as e:  # noqa: BLE001 — the assert below says it
            errors.append(repr(e))

    before = _get(served, "/debug/vars")["stacked"]
    threads = [threading.Thread(target=client, args=(n,), daemon=True)
               for n in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not wrong
    after = _get(served, "/debug/vars")["stacked"]
    assert after["evictions"] > before["evictions"]
    assert after["stack_bytes"] <= small_budget
    hbm = _get(served, "/debug/hbm")
    assert hbm["total_bytes"] == after["stack_bytes"] + after[
        "rows_stack_bytes"]


# ---------------------------------------------- where the budgets come from


GIB = 1 << 30


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("reported,local_devices,want", [
    # a v5e: a quarter and an eighth of what one device reports
    (16 * GIB, 1, (4 * GIB, 2 * GIB)),
    # four devices share every stack: four shares
    (16 * GIB, 4, (16 * GIB, 8 * GIB)),
    # an odd size: whole bytes, the shares of one device times the devices
    (1001, 4, (1001 // 4 * 4, 1001 // 8 * 4)),
    # the host CPU reports none: the constants stand
    (None, 8, (512 * MIB, 256 * MIB)),
])
def test_budgets_are_shares_of_what_the_device_reports(
        monkeypatch, reported, local_devices, want):
    monkeypatch.setattr(device, "_MEMORY", reported)
    monkeypatch.setattr(device, "_FACTS", {
        "platform": "tpu", "deviceKind": "fake", "deviceCount": local_devices,
        "localDeviceCount": local_devices})
    assert stacked.budgets() == want
    ev = stacked.StackedEvaluator()
    assert ev._pool(("leaf",))[1] == want[0]
    assert ev._pool(("rows",))[1] == want[1]
    stats = ev.cache_stats()
    assert (stats["stack_budget_bytes"],
            stats["rows_stack_budget_bytes"]) == want
    hbm = ev.hbm_snapshot()
    assert (hbm["stack_budget_bytes"], hbm["rows_stack_budget_bytes"],
            hbm["device_bytes_limit"]) == (*want, reported)


@pytest.mark.parametrize("stats,want", [
    ([{"bytes_limit": 16 * GIB, "bytes_in_use": 5}], 16 * GIB),
    ([{"bytes_limit": 16 * GIB}, {"bytes_limit": 15 * GIB}], 15 * GIB),
    ([None], None),                 # XLA:CPU
    ([{"bytes_in_use": 5}], None),  # a backend that reports no limit
])
def test_the_device_report_is_read_once_and_never_before_a_backend(
        monkeypatch, stats, want):
    import jax

    reads = []

    def local_devices():
        reads.append(1)
        return [_FakeDevice(s) for s in stats]

    monkeypatch.setattr(jax, "local_devices", local_devices)
    monkeypatch.setattr(device, "_MEMORY", device._UNREAD)
    monkeypatch.setattr(device, "backends_are_initialized", lambda: False)
    assert device.memory_bytes() is None and not reads  # nothing initialised
    assert stacked.budgets() == (stacked.MAX_STACK_BYTES,
                                 stacked.MAX_ROWS_STACK_BYTES)
    monkeypatch.setattr(device, "backends_are_initialized", lambda: True)
    assert device.memory_bytes() == want
    assert device.memory_bytes() == want and len(reads) == 1


# ----------------------------------------- the build path: spans, counters


def test_a_cold_build_is_counted_and_its_spans_say_where_it_went(served):
    ev = served.api.executor._stacked
    ev.invalidate()
    pql = "Count(Intersect(Row(f=3), Row(g=4)))"
    before = ev.cache_stats()
    reply = _ask(served, pql, profile=True)
    assert reply["results"][0] == served.expected[pql]
    found = []

    def walk(node):
        if node["name"] == "stack.lookup":
            found.append((node["tags"]["outcome"], [
                (c["name"], c["tags"]) for c in node["children"]]))
        for child in node["children"]:
            walk(child)

    walk(reply["profile"]["spans"])
    stack_bytes = _working_set(served) // 8
    planes = SHARDS
    assert found == [("build", [
        ("stack.gather", {"planes": planes}),
        ("stack.place", {"bytes": stack_bytes, "repr": "dense"})])] * 2
    after = ev.cache_stats()
    assert after["builds"] - before["builds"] == 2
    build = after["build_seconds"] - before["build_seconds"]
    gather = after["build_gather_seconds"] - before["build_gather_seconds"]
    assert 0 < gather < build
    # a hit builds nothing
    assert _ask(served, pql)["results"][0] == served.expected[pql]
    assert ev.cache_stats()["builds"] == after["builds"]
    # a write to one shard patches: spans, but no build counted
    served.client.import_bits(served.index, "f", [3], [5])
    found.clear()
    walk(_ask(served, pql, profile=True)["profile"]["spans"])
    assert found[0] == ("patch", [
        ("stack.gather", {"planes": 1}),
        ("stack.place", {"bytes": stack_bytes, "repr": "dense"})])
    assert found[1] == ("hit", [])
    assert ev.cache_stats()["builds"] == after["builds"]
    assert ev.cache_stats()["patches"] == after["patches"] + 1


# --------------------------------------------------- the benchmark's readers


def _context(bench, before, after, queries=0):
    record = [0, "query", 1.0, 2.0, 200, 7, 0.0, 7, "Count(Row(f=1))"]
    run = types.SimpleNamespace(
        cell={}, config={}, spec={}, device={}, opened=0.0, closed=40.0,
        t0=-80.0, window=[list(record) for _ in range(queries)],
        profiles=[], before={"vars": {"stacked": before}},
        after={"vars": {"stacked": after}}, trace=None, traced_queries=[])
    return bench.cell.Context(run)


BEFORE = {"evictions": 3, "stack_bytes": 0}
AFTER = {"evictions": 53, "stack_bytes": 1_000, "stack_budget_bytes": 4_000,
         "builds": 8, "build_seconds": 20.0, "build_gather_seconds": 15.0}
READERS = {  # metric -> (its value on BEFORE/AFTER and 200 queries, moves)
    "stack.evictions_per_query": (50 / 200, "query_qps"),
    "stack.resident_share": (25.0, "query_qps"),
    "stack.build_s": (2.5, "setup_s"),
    "stack.gather_share": (75.0, "setup_s"),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_of_a_build_path_metric(bench, name):
    """The number by hand; nothing (and no error) from a program that
    lacks the counter, as the parent commit does."""
    read = bench.manifest.reader(name)
    value, moves = READERS[name]
    assert read(_context(bench, BEFORE, AFTER, 200)) == pytest.approx(value)
    parent = {k: v for k, v in AFTER.items()
              if k in ("evictions", "stack_bytes")}
    if name == "stack.evictions_per_query":     # the parent counts these
        assert read(_context(bench, BEFORE, parent, 200)) == 0.25
        assert read(_context(bench, BEFORE, parent, 0)) is None
    else:
        assert read(_context(bench, BEFORE, parent, 200)) is None
    assert read(_context(bench, {}, {}, 200)) is None
    entry = [m for m in bench.listed["per_layer"] if m["name"] == name][0]
    assert entry["workloads"] == [CELL] and entry["moves"] == moves
    assert entry["layer"] == "stack cache" and entry["better"] == "lower"


def test_the_cell_is_entered_beside_its_control(bench):
    listed = bench.listed
    cell = bench.manifest.cell(listed, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    reports = {m["name"] for m in bench.manifest.metrics(
        listed, "end_to_end", CELL)}
    assert reports == {"query_qps", "setup_s"}
    control = {m["name"] for m in bench.manifest.metrics(
        listed, "per_layer", "seg1b-read-c32")}
    mine = {m["name"] for m in bench.manifest.metrics(
        listed, "per_layer", CELL)}
    assert mine == control | set(READERS) | {"path.p95_ms"}
    # the guarantees are segment-1b's, word for word
    base = bench.manifest.config(listed, "segment-1b")
    full = bench.manifest.config(listed, CONFIG)
    for key in ("guarantees", "server_flags", "append_counter", "counters",
                "replicas", "shards", "shard_width", "columns", "fields",
                "word_density", "data_module", "index", "chips"):
        assert full[key] == base[key], key

"""`taxi-250m` at a small size on the host CPU (ISSUE 38): the benchmark's
configuration and traffic files, the data its loader sends through
`import_roaring`, every one of the traffic's 20 GroupBy and TopN queries
asked over HTTP and of the executor directly and compared with the plain
reference (`benchmark/harness/taxi_reference.py`: numpy over per-ride
values, nothing of the program); the reference against a per-ride loop;
answers a count or a rank off fail the comparison; the counters, the
program's name in the trace, and the new metrics' readers."""

import collections
import json
import os
import subprocess
import sys
import types
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CONFIG, TRAFFIC, CELL = "taxi-250m", "taxi-rank-c32", "taxi250m-rank-c32"
SEED = 2147491038          # past 2**31, as the driver's seeds are
SHARDS = 8
WAIT = 120
REHEARSAL_EXIT = 3
NEW_METRICS = ("path.call_ms.groupby", "path.call_ms.topn",
               "stack.rows_hit_rate", "kernel.roofline_share.scan",
               "holder.row_set_hit_rate")
# accepted metrics this cell is listed on besides the generic ones: the
# CPU split of the spans table and the dispatch lock's wait (PR 38's review)
LISTED = ("exec.dispatches_per_query", "api.cpu_ms", "exec.cpu_ms",
          "stack.cpu_ms", "dispatch.cpu_ms", "dispatch.lock_wait_ms")
SCAN_FAULTS = ("scan-low-precision", "scan-plus-one")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported as its files import them,
    with the configuration at 8 shards and the traffic file."""
    for path in (BENCH, os.path.join(BENCH, "data")):
        sys.path.insert(0, path)
    try:
        import taxi
        from harness import (cell, manifest, roofline_scan, stats,
                             taxi_reference, traffic)

        listed = manifest.load()
        full = manifest.config(listed, CONFIG)
        config = manifest.config(listed, CONFIG)
        config["shards"] = SHARDS
        spec = traffic.load(manifest.traffic_path(TRAFFIC))
        yield types.SimpleNamespace(
            taxi=taxi, ref=taxi_reference, cell=cell, manifest=manifest,
            stats=stats, roofline_scan=roofline_scan,
            traffic=traffic, listed=listed, full=full, config=config,
            spec=spec, pqls=traffic.distinct_queries(spec))
    finally:
        for path in (BENCH, os.path.join(BENCH, "data")):
            sys.path.remove(path)


def _queries():
    with open(os.path.join(BENCH, "traffic", TRAFFIC + ".json")) as f:
        spec = json.load(f)
    out = []
    for op in spec["operations"]:
        (name, arg), = op["args"].items()
        out += [op["pql"].format(**{name: v}) for v in arg["choice"]]
    return sorted(out)


QUERIES = _queries()


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """One in-process server holding the 8-shard index, every fragment
    built and sent as the benchmark's loader builds and sends it, and the
    reference's answer to each of the 20 distinct queries."""
    from tests.harness import ServerHarness

    cfg = bench.config
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("taxi")))
    try:
        h.client.create_index(cfg["index"])
        for field in sorted(cfg["fields"]):
            h.client.create_field(cfg["index"], field, cfg["field_options"])
        sent = acknowledged = 0
        for blobs in map(bench.taxi._blobs,
                         [(cfg, SEED, [s]) for s in range(SHARDS)]):
            for field, shard, blob, bits in blobs:
                acknowledged += h.client.import_roaring(
                    cfg["index"], field, shard, blob)["changed"]
                sent += bits
        assert sent == acknowledged == 4 * SHARDS * cfg["shard_width"]
        h.expected = bench.taxi.expected(cfg, SEED, bench.pqls)
        h.index = cfg["index"]
        yield h
    finally:
        h.close()


def _ask(h, pql):
    req = urllib.request.Request(f"{h.address}/index/{h.index}/query",
                                 data=pql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=WAIT) as r:
        return json.loads(r.read())["results"][0]


def _stacked(h):
    with urllib.request.urlopen(h.address + "/debug/vars",
                                timeout=WAIT) as r:
        return json.loads(r.read())["stacked"]


# ------------------------------------------------------------- the files


def test_the_files_say_what_the_cell_is(bench):
    full = bench.full
    assert bench.pqls == QUERIES and len(QUERIES) == 20
    width = full["shard_width"]
    assert full["shards"] == -(-full["rides"] // width) == 239
    assert full["columns"] == full["shards"] * width
    assert bench.taxi.rides_in(full, 238) == 438_912
    assert bench.taxi.rides_in(full, 237) == width
    stack = full["shards"] * width // 8
    rows = {f: len(v["rows"]) for f, v in full["fields"].items()}
    assert rows == {"passenger_count": 10, "pickup_year": 8,
                    "pickup_month": 12, "dist_miles": 32}
    resident = full["resident_bytes"]
    assert resident["row_stack"] == stack == 31_326_208
    assert resident["rows_pool"] == (10 + 8 + 32) * stack
    assert resident["leaf_pool"] == (12 + 8) * stack
    assert resident["rows_budget_on_v5e"] == 16_909_336_064 // 8
    assert resident["rows_pool"] <= resident["rows_budget_on_v5e"]
    for spec in full["fields"].values():
        assert len(spec["shares"]) == len(spec["rows"])
        assert spec["rows"] == sorted(spec["rows"])
    entry = [c for c in bench.listed["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == list(full["reduced"])
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
    # the segment configurations' guarantees, but for what "exact" covers
    base = bench.manifest.config(bench.listed, "segment-1b")
    for key in ("durability", "read_your_write", "replicas"):
        assert full["guarantees"][key] == base["guarantees"][key]
    for key in ("server_flags", "counters", "append_counter", "replicas",
                "chips"):
        assert full[key] == base[key], key


def test_the_cell_reports_what_it_reads(bench):
    listed = bench.listed
    cell = bench.manifest.cell(listed, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert {m["name"] for m in bench.manifest.metrics(
        listed, "end_to_end", CELL)} == {"query_qps", "setup_s"}
    mine = {m["name"]: m for m in bench.manifest.metrics(
        listed, "per_layer", CELL)}
    assert set(NEW_METRICS) <= set(mine)
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "query_qps"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    # nothing that parses a Count is asked to read a GroupBy
    assert "kernel.roofline_share" not in mine


@pytest.mark.parametrize("name", LISTED)
def test_the_cell_is_appended_to(bench, name):
    """An accepted metric the cell reports: the cell appended to its
    list, nothing else about it changed. Cells added later are appended
    after it, so the list keeps the order of BENCHMARK.json's cells."""
    entry, = [m for m in bench.listed["per_layer"] if m["name"] == name]
    order = [c["name"] for c in bench.listed["workloads"]]
    assert entry["workloads"] == sorted(entry["workloads"], key=order.index)
    assert entry["workloads"].count(CELL) == 1
    assert entry["moves"] == "query_qps"


# ------------------------------------------------- the data, the reference


@pytest.mark.parametrize("seed", [7, SEED, 2**31 + 2**20])
def test_the_data_is_deterministic_from_the_seed(bench, seed):
    cfg = dict(bench.config, shard_width=4096, rides=3 * 4096 + 100,
               shards=4)
    one = bench.taxi.shard_columns(cfg, seed, 2)
    again = bench.taxi.shard_columns(cfg, seed, 2)
    other = bench.taxi.shard_columns(cfg, seed + 1, 2)
    assert sorted(one) == sorted(cfg["fields"])
    for field, values in one.items():
        assert np.array_equal(values, again[field])
        assert not np.array_equal(values, other[field])
        assert len(values) == 4096
        assert set(np.unique(values)) <= set(cfg["fields"][field]["rows"])
    # the last shard holds what is left of the rides; blobs repeat too
    assert len(bench.taxi.shard_columns(cfg, seed, 3)["dist_miles"]) == 100
    blobs = bench.taxi._blobs((cfg, seed, [3]))
    assert blobs == bench.taxi._blobs((cfg, seed, [3]))
    assert [b[3] for b in blobs] == [100] * 4


def test_the_shares_are_drawn_as_the_configuration_states(bench):
    cfg = dict(bench.config, rides=1 << 20, shards=1)
    columns = bench.taxi.shard_columns(cfg, SEED, 0)
    for field, spec in cfg["fields"].items():
        want = np.asarray(spec["shares"], float) / sum(spec["shares"])
        got = np.asarray([np.mean(columns[field] == r)
                          for r in spec["rows"]])
        assert np.abs(got - want).max() < 0.003, field


def _loop_answer(pql, rides, cfg):
    """The per-ride Python loop the reference has to agree with."""
    kind = pql.split("(", 1)[0]
    filt_field, filt_row = pql.rsplit("Row(", 1)[1].split(")")[0].split("=")
    counts = collections.Counter()
    if kind == "GroupBy":
        a, b = [p.split(")")[0] for p in pql.split("Rows(")[1:]]
    else:
        a, b = pql[len("TopN("):].split(",")[0], None
    for ride in rides:
        if ride[filt_field] != int(filt_row):
            continue
        counts[(ride[a], ride[b]) if b else ride[a]] += 1
    if kind == "GroupBy":
        return [{"group": [{"field": a, "rowID": ra},
                           {"field": b, "rowID": rb}], "count": n}
                for (ra, rb), n in sorted(counts.items())]
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    return [{"id": r, "count": n} for r, n in ranked[:10]]


@pytest.fixture(scope="module")
def tiny(bench):
    cfg = dict(bench.config, shard_width=2048, rides=2 * 2048 + 700,
               shards=3)
    rides = []
    queries = {pql: bench.ref.parse(pql) for pql in QUERIES}
    counts = bench.ref.tables(queries.values(), cfg)
    for shard in range(cfg["shards"]):
        columns = bench.taxi.shard_columns(cfg, SEED, shard)
        bench.ref.tally(counts, columns, cfg)
        names = sorted(columns)
        rides += [dict(zip(names, map(int, ride)))
                  for ride in zip(*(columns[n] for n in names))]
    assert len(rides) == cfg["rides"]
    return cfg, rides, queries, counts


@pytest.mark.parametrize("pql", QUERIES)
def test_the_reference_agrees_with_a_per_ride_loop(bench, tiny, pql):
    cfg, rides, queries, counts = tiny
    want = _loop_answer(pql, rides, cfg)
    assert want
    assert bench.ref.answer(queries[pql], counts, cfg) == want


@pytest.mark.parametrize("pql", QUERIES)
def test_a_count_off_by_one_or_two_ranks_swapped_fail(bench, tiny, pql):
    cfg, _, queries, counts = tiny
    want = bench.ref.answer(queries[pql], counts, cfg)
    bad = json.loads(json.dumps(want))
    if pql.startswith("GroupBy"):
        bad[len(bad) // 2]["count"] += 1
    else:
        bad[3], bad[4] = bad[4], bad[3]
    record = [0, "query", 1.0, 2.0, 200, bad, 0.0, want, pql]
    assert not bench.stats.ok(record)
    record[bench.stats.ANSWER] = json.loads(json.dumps(want))
    assert bench.stats.ok(record)


def test_topn_ties_rank_by_id(bench):
    cfg = dict(bench.config, shard_width=64, rides=6, shards=1)
    columns = {"pickup_year": np.full(6, 2012, np.uint16),
               "dist_miles": np.asarray([5, 3, 5, 3, 9, 1], np.uint16)}
    query = bench.ref.parse("TopN(dist_miles, Row(pickup_year=2012), n=3)")
    counts = bench.ref.tables([query], cfg)
    bench.ref.tally(counts, columns, cfg)
    want = [{"id": 3, "count": 2}, {"id": 5, "count": 2},
            {"id": 1, "count": 1}]
    assert bench.ref.answer(query, counts, cfg) == want
    swapped = [want[1], want[0], want[2]]
    assert not bench.stats.ok(
        [0, "query", 1.0, 2.0, 200, swapped, 0.0, want, "TopN"])


# ---------------------------------------------------- the server at 8 shards


@pytest.mark.parametrize("via", ["http", "executor"])
@pytest.mark.parametrize("pql", QUERIES)
def test_every_query_equals_the_reference(served, pql, via):
    if via == "http":
        got = _ask(served, pql)
    else:
        result, = served.api.executor.execute(served.index, pql)
        got = [r.to_json() for r in result]
    assert got == served.expected[pql]


def test_the_counters_see_the_scans(served):
    """Every row chunk resident after one pass: a second pass only hits.
    8 shards: one row chunk holds every candidate, so a call is one
    filter and one scan launch (`exec.dispatches_per_query` 2.0)."""
    for pql in QUERIES:
        _ask(served, pql)
    before = _stacked(served)
    for pql in QUERIES:
        assert _ask(served, pql) == served.expected[pql]
    after = _stacked(served)

    def delta(key):
        return after[key] - before[key]

    assert delta("dispatches") == len(QUERIES) * 2
    assert delta("rows_lookups") == 12 * 2 + 8
    assert delta("rows_hits") == delta("rows_lookups")
    assert delta("evictions") == 0
    assert after["rows_stack_bytes"] <= after["rows_stack_budget_bytes"]


GROUPBY_239 = "GroupBy(Rows(a), Rows(b), filter=Row(m=1))"


@pytest.fixture(scope="module")
def at_239_shards(tmp_path_factory):
    """The configuration's 239 shards: fields a and b with rows 0 and 1,
    the filter m, and every stack resident after one GroupBy."""
    from tests.harness import ServerHarness

    shards = 239
    cols = [s << 20 for s in range(shards)]
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("t239")))
    try:
        h.client.create_index("t")
        for field in ("a", "b", "m"):
            h.client.create_field("t", field)
        for field, rows in (("a", [0, 1]), ("b", [0, 1]), ("m", [1, 1])):
            h.client.import_bits("t", field, [rows[0]] * shards
                                 + [rows[1]] * shards,
                                 cols + [c + 1 for c in cols])
        h.client.query("t", GROUPBY_239)  # stacks resident, as in a window
        h.shards = shards
        yield h
    finally:
        h.close()


def _profiled_groupby(h):
    reply = h.client.query("t", GROUPBY_239, profile=True)
    assert [g["count"] for g in reply["results"][0]] == [h.shards] * 2
    names = collections.Counter()
    todo = [reply["profile"]["spans"]]
    while todo:
        node = todo.pop()
        names[node["name"]] += 1
        todo += node.get("children", [])
    for stage in ("executor.executeGroupBy", "stack.lookup",
                  "dispatch.lock_wait", "stacked.kernel", "dispatch.fetch"):
        assert names[stage] >= 1, stage
    return reply, names


def test_a_239_shard_groupby_profile_keeps_its_stages(at_239_shards):
    """The first GroupBy after a write to `a` walks `a`'s 239 fragments
    again (its view's tick moved): ≈ 240 `workpool.task` spans before any
    stage span. Past the profile's task leaves they are dropped, so the
    lock wait, kernel and fetch that follow (what `dispatch.*` and
    `exec.self_ms` read) stay in the tree."""
    from pilosa_tpu.utils import profile

    h = at_239_shards
    # row 0 of `a` in a column that neither `b` nor `m` holds: the groups
    # and their counts stay, the view's tick moves
    h.client.import_bits("t", "a", [0], [5])
    reply, names = _profiled_groupby(h)
    assert names["workpool.task"] == profile.MAX_TASK_LEAVES
    assert reply["profile"]["spansDropped"] > 0


def test_a_warm_239_shard_groupby_walks_nothing(at_239_shards):
    """Nothing written since the last GroupBy: both `Rows()` answer from
    their views' kept sets and the call opens no `workpool.task`."""
    h = at_239_shards
    h.client.query("t", GROUPBY_239)
    reply, names = _profiled_groupby(h)
    assert names["workpool.task"] == 0
    assert reply["profile"].get("spansDropped", 0) == 0


@pytest.mark.parametrize("fault", SCAN_FAULTS)
def test_a_broken_scan_reads_incorrect(fault):
    """`run.py`'s own comparison catches a wrong scan count: the cell's
    faults (`harness/scan_faults.py`), at 8 shards."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/harness/scan_faults.py", "--workload",
         CELL, "--seed", str(SEED), "--seconds", "3", "--trace", "0",
         "--rehearse-shards", str(SHARDS), "--fault", fault],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == REHEARSAL_EXIT, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["fault"] == fault and line["correct"] is False
    failing = {k for k, v in line["compared"].items()
               if v["value"] > v["limit"]}
    assert "wrong_answers" in failing
    assert failing <= {"wrong_answers", "nothing_answered"}


def test_run_rehearses_the_cell(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(SEED), "--seconds", "3", "--trace", "1",
         "--rehearse-shards", str(SHARDS)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == REHEARSAL_EXIT, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert all(v["value"] == 0 for v in line["compared"].values())
    readable = set(line["metrics_readable"])
    # the scan's roofline needs the chip's peak: nothing on the host
    assert set(NEW_METRICS) - readable == {"kernel.roofline_share.scan"}
    assert set(LISTED) <= readable
    assert "kernel.pairwise_launches_per_call" in readable


# ----------------------------------------------------- the program's name


@pytest.mark.parametrize("has_filt", [False, True])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_the_pairwise_program_carries_its_name(backend, has_filt):
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitplane

    # the kernel lowers through the Pallas interpreter off a TPU
    kind = "jnp" if backend == "jnp" else "interpret"
    a = (jnp.zeros((3, 2, 32768), jnp.uint32),)
    b = (jnp.zeros((2, 2, 32768), jnp.uint32),)
    args = (a, b, jnp.zeros((2, 32768), jnp.uint32)) if has_filt else (a, b)
    text = bitplane._pairwise_fn(has_filt, kind).lower(*args).as_text(
        debug_info=True)
    assert "module @jit_pairwise_counts " in text
    assert 'loc("jit(pairwise_counts)/pairwise_counts/' in text
    assert "jit_fn" not in text and "jit(fn)" not in text


# ------------------------------------------------------------- the readers


def _context(bench, before, after, profiles=(), trace=None, queries=(),
             device=None, config=None, section="stacked"):
    run = types.SimpleNamespace(
        cell={}, config=config or {}, spec={},
        device=device or {"platform": "cpu"}, opened=0.0, closed=40.0,
        t0=-80.0, window=[], profiles=list(profiles),
        before={"vars": {section: before}},
        after={"vars": {section: after}}, trace=trace,
        traced_queries=list(queries))
    return bench.cell.Context(run)


# name: (the /debug/vars section, before, after, what the reader gives)
COUNTED = {
    "stack.rows_hit_rate": ("stacked", {"rows_lookups": 10, "rows_hits": 8},
                            {"rows_lookups": 410, "rows_hits": 408}, 1.0),
    "holder.row_set_hit_rate": (
        "holder", {"row_set_walks": 7, "row_set_hits": 3},
        {"row_set_walks": 9, "row_set_hits": 201, "row_set_seconds": 0.5},
        198 / 200),
    "kernel.pairwise_launches_per_call": (
        "stacked", {"pairwise_dispatches": 12, "pairwise_calls": 2},
        {"pairwise_dispatches": 52, "pairwise_calls": 22}, 40 / 20),
}


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_reader_of_a_counter(bench, name):
    section, before, after, want = COUNTED[name]
    read = bench.manifest.reader(name)
    assert read(_context(bench, before, after, section=section)) \
        == pytest.approx(want)
    # a parent commit has neither counter: nothing, and no error
    parent = {"hits": 5, "misses": 0, "cache_flush_seconds": 1.0}
    assert read(_context(bench, parent, parent, section=section)) is None
    assert read(_context(bench, before, before, section=section)) is None


@pytest.mark.parametrize("name,span", [
    ("path.call_ms.groupby", "executor.executeGroupBy"),
    ("path.call_ms.topn", "executor.executeTopN")])
def test_reader_of_a_call_span(bench, name, span):
    read = bench.manifest.reader(name)
    profiles = [{span: [s, 0.0], "api.Query": [1.0, 0.1]}
                for s in (0.010, 0.030, 0.020)]
    profiles.append({"executor.executeCount": [9.0, 9.0]})
    assert read(_context(bench, {}, {}, profiles)) == pytest.approx(20.0)
    assert read(_context(bench, {}, {}, [])) is None


def test_reader_of_the_scan_roofline(bench):
    full = bench.full
    groupby, topn = QUERIES[0], QUERIES[-1]
    assert groupby.startswith("GroupBy") and topn.startswith("TopN")
    stack = 239 * 32768 * 4
    assert bench.roofline_scan.query_bytes(groupby, full) == 19 * stack
    assert bench.roofline_scan.query_bytes(topn, full) == 33 * stack
    read = bench.manifest.reader("kernel.roofline_share.scan")
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    least = 52 * stack / 819e9
    trace = {"busy_s": least * 4}
    got = read(_context(bench, {}, {}, trace=trace,
                        queries=[groupby, topn], device=tpu, config=full))
    assert got == pytest.approx(25.0)
    # nothing on the host CPU, without a trace, or without a query in it
    assert read(_context(bench, {}, {}, trace=trace, queries=[groupby],
                         config=full)) is None
    assert read(_context(bench, {}, {}, queries=[groupby], device=tpu,
                         config=full)) is None
    assert read(_context(bench, {}, {}, trace=trace, device=tpu,
                         config=full)) is None

"""`ssb-sf40` at a small size on the host CPU: the benchmark's
configuration and traffic files; the loader's int-field blobs against the
same values sent through `import_values`, bit for bit in the `bsig_` views
and answer for answer; every one of the traffic's 248 SSB flight-1 Sums
asked over HTTP and of the executor directly and compared with the plain
reference (`benchmark/harness/ssb_reference.py`: numpy over per-row
values, nothing of the program); the reference against a per-row loop;
the Sum path's counters and span; the new metrics' readers and the
roofline's bytes; the Sum faults reading `correct` false."""

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
CONFIG, TRAFFIC, CELL = "ssb-sf40", "ssb-flight1-c32", "ssb40-flight1-c32"
SEED = 2147491038          # past 2**31, as the driver's seeds are
SHARDS = 8
WAIT = 120
REHEARSAL_EXIT = 3
NEW_METRICS = ("path.call_ms.sum", "exec.launches_per_call.sum",
               "kernel.roofline_share.bsi")
# the accepted metrics the cell is appended to: taxi's generic ones
LISTED = ("gen.turnaround_ms", "edge.query_server_ms", "edge.cpu_ms",
          "api.self_ms", "exec.self_ms", "exec.dispatches_per_query",
          "dispatch.compiles_in_window", "kernel.device_ms_per_query",
          "proc.cpu_ms_per_query", "proc.request_cpu_ms",
          "proc.runtime_cpu_ms", "proc.background_cpu_ms", "api.cpu_ms",
          "exec.cpu_ms", "stack.cpu_ms", "dispatch.cpu_ms",
          "dispatch.lock_wait_ms", "dispatch.fetch_ms",
          "flush.seconds_in_window", "stack.evictions_per_query",
          "stack.resident_share", "stack.build_s")
SUM_FAULTS = ("sum-low-precision", "sum-plus-one")
DISC = [f"{d} <= lo_discount <= {d + 2}" for d in range(1, 9)]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own modules, imported as its files import them,
    with the configuration at 8 shards and the traffic file."""
    for path in (BENCH, os.path.join(BENCH, "data")):
        sys.path.insert(0, path)
    try:
        import ssb
        from harness import (cell, manifest, roofline_bsi, ssb_reference,
                             stats, traffic)

        listed = manifest.load()
        full = manifest.config(listed, CONFIG)
        config = manifest.config(listed, CONFIG)
        config["shards"] = SHARDS
        spec = traffic.load(manifest.traffic_path(TRAFFIC))
        yield types.SimpleNamespace(
            ssb=ssb, ref=ssb_reference, cell=cell, manifest=manifest,
            stats=stats, roofline_bsi=roofline_bsi, traffic=traffic,
            listed=listed, full=full, config=config, spec=spec,
            pqls=traffic.distinct_queries(spec))
    finally:
        for path in (BENCH, os.path.join(BENCH, "data")):
            sys.path.remove(path)


def _queries():
    """The flight-1 Sums written out by hand from the SSB's three
    queries and the traffic's parameter domains."""
    out = []
    for d in DISC:
        out += [f"Sum(Intersect(Row(lo_year={y}), Row({d}), "
                f"Row(lo_quantity < 25)), field=lo_disc_revenue)"
                for y in range(1992, 1999)]
        out += [f"Sum(Intersect(Row(lo_yearmonth={ym}), Row({d}), "
                f"Row(26 <= lo_quantity <= 35)), field=lo_disc_revenue)"
                for ym in range(199401, 199413)]
        out += [f"Sum(Intersect(Row(lo_weeknum={w}), Row(lo_year=1994), "
                f"Row({d}), Row(26 <= lo_quantity <= 35)), "
                f"field=lo_disc_revenue)" for w in range(1, 13)]
    return sorted(out)


QUERIES = _queries()


def _ask(h, index, pql):
    req = urllib.request.Request(f"{h.address}/index/{index}/query",
                                 data=pql.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=WAIT) as r:
        return json.loads(r.read())["results"][0]


def _vars(h):
    with urllib.request.urlopen(h.address + "/debug/vars",
                                timeout=WAIT) as r:
        return json.loads(r.read())


def _send(h, bench, cfg, index, shards, int_fields=True):
    """Send the loader's blobs of `shards` into `index` (the schema made
    as `load` makes it); returns (bits sent, bits acknowledged)."""
    sent = acknowledged = 0
    for shard in shards:
        for field, view, s, blob, bits in bench.ssb._blobs(
                (cfg, SEED, [shard])):
            if view != "standard" and not int_fields:
                continue
            acknowledged += h.client.import_roaring(
                index, field, s, blob, view=view)["changed"]
            sent += bits
    return sent, acknowledged


# ------------------------------------------------------------- the files


def test_the_files_say_what_the_cell_is(bench):
    from pilosa_tpu.core.field import FieldOptions

    full = bench.full
    assert bench.pqls == QUERIES and len(QUERIES) == 248
    width = full["shard_width"]
    assert full["rows"] == 6_000_000 * full["scale_factor"]
    assert full["shards"] == -(-full["rows"] // width) == 172
    assert full["columns"] == full["shards"] * width
    assert bench.ssb.rows_in(full, 171) == 693_504
    assert bench.ssb.rows_in(full, 170) == width
    assert full["parts"] == 200_000 * int(1 + np.log2(full["scale_factor"]))
    # the bit depths and bases the server derives from min and max
    depths = {}
    for field, spec in full["int_fields"].items():
        opts = FieldOptions.int_field(spec["min"], spec["max"])
        assert (opts.base, opts.bit_depth) == (spec["base"],
                                               spec["bit_depth"]), field
        depths[field] = spec["bit_depth"]
    assert depths == {"lo_discount": 4, "lo_quantity": 6,
                      "lo_disc_revenue": 27}
    # the measure's range: 50 x the retail price's bound (90,000 + 20,000
    # + 99,900 c) x 10 %; of 1M part keys the dearest costs 209,899 c
    parts = np.arange(1, full["parts"] + 1)
    dearest = int(bench.ssb.retail_price_cents(parts).max())
    assert dearest == 209_899
    assert full["int_fields"]["lo_disc_revenue"]["max"] == 50 * 209_900 * 10
    plane = 176 * 32768 * 4                 # 172 shards padded to 8s
    resident = full["resident_bytes"]
    assert resident["row_plane"] == plane
    assert resident["bsi_stacks"] == sum(d + 2 for d in depths.values()) \
        * plane
    assert resident["leaf_pool"] == (7 + 12 + 12) * plane
    assert resident["total"] == resident["bsi_stacks"] \
        + resident["leaf_pool"]
    assert resident["leaf_budget_on_v5e"] == 16_909_336_064 // 4
    assert resident["total"] <= resident["leaf_budget_on_v5e"]
    entry, = [c for c in bench.listed["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == list(full["reduced"])
    assert entry["source"] == full["source"] and len(entry["source"]) <= 200
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
    assert set(mine) == set(NEW_METRICS) | set(LISTED)
    for name in NEW_METRICS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["moves"] == "query_qps"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert [m["name"] for m in listed["per_layer"][-3:]] \
        == list(NEW_METRICS)


@pytest.mark.parametrize("name", LISTED)
def test_the_cell_is_appended_to(bench, name):
    """An accepted metric the cell reports: the cell appended to its
    list after taxi's, nothing else about it changed."""
    entry, = [m for m in bench.listed["per_layer"] if m["name"] == name]
    assert entry["workloads"][-2:] == ["taxi250m-rank-c32", CELL]
    assert entry["workloads"].count(CELL) == 1


# ------------------------------------------------- the data, the reference


@pytest.mark.parametrize("seed", [7, SEED, 2**31 + 2**20])
def test_the_data_is_deterministic_from_the_seed(bench, seed):
    cfg = dict(bench.config, rows=2 * 2**20 + 100, shards=3)
    one = bench.ssb.shard_columns(cfg, seed, 2)
    again = bench.ssb.shard_columns(cfg, seed, 2)
    other = bench.ssb.shard_columns(cfg, seed + 1, 2)
    assert sorted(one) == ["lo_disc_revenue", "lo_discount", "lo_quantity",
                           "orderdate"]
    for column, values in one.items():
        assert np.array_equal(values, again[column])
        assert not np.array_equal(values, other[column])
        assert len(values) == 100


def test_the_rows_are_drawn_as_the_configuration_states(bench):
    cfg = dict(bench.config, rows=2**20, shards=1)
    columns = bench.ssb.shard_columns(cfg, SEED, 0)
    dates = bench.ssb.date_table(cfg)
    assert len(dates["year"]) == 2406
    assert dates["year"][0] == 1992 and dates["year"][-1] == 1998
    assert dates["yearmonth"][-1] == 199808
    assert dates["weeknum"].min() == 1 and dates["weeknum"].max() == 53
    # 1992-12-31 is day 366 of a leap year: week 53
    assert dates["weeknum"][365] == 53 and dates["weeknum"][366] == 1
    for column, lo, hi in (("orderdate", 0, 2405), ("lo_discount", 0, 10),
                           ("lo_quantity", 1, 50)):
        values = columns[column]
        assert values.min() == lo and values.max() == hi
        share = np.bincount(values.astype(np.int64) - lo) / len(values)
        assert np.abs(share - 1 / (hi - lo + 1)).max() < 0.2 / (hi - lo + 1)
    revenue = columns["lo_disc_revenue"]
    assert revenue.min() == 0
    assert np.all(revenue % np.maximum(columns["lo_discount"], 1) == 0)
    assert np.all(revenue[columns["lo_discount"] == 0] == 0)
    price = revenue[columns["lo_discount"] > 0] // (
        columns["lo_discount"] * columns["lo_quantity"])[
        columns["lo_discount"] > 0]
    assert price.min() >= 90_000 and price.max() <= 209_900


@pytest.fixture(scope="module")
def tiny(bench):
    """One small shard's rows as dicts, and the reference's table."""
    cfg = dict(bench.config, rows=20_000, shards=1)
    columns = bench.ssb.shard_columns(cfg, SEED, 0)
    dates = bench.ssb.date_table(cfg)
    table = bench.ref.Table(cfg, dates)
    table.tally(columns)
    rows = [{"lo_year": int(dates["year"][day]),
             "lo_yearmonth": int(dates["yearmonth"][day]),
             "lo_weeknum": int(dates["weeknum"][day]),
             "lo_discount": int(disc), "lo_quantity": int(qty),
             "lo_disc_revenue": int(rev)}
            for day, disc, qty, rev in zip(
                columns["orderdate"], columns["lo_discount"],
                columns["lo_quantity"], columns["lo_disc_revenue"])]
    return rows, table


def _loop_answer(pql, rows):
    """The per-row Python loop the reference has to agree with, reading
    the query text itself."""
    body = pql[len("Sum(Intersect("):pql.rindex("), field=")]
    measure = pql[pql.rindex("field=") + len("field="):-1]
    tests = []
    for cond in body[len("Row("):-1].split("), Row("):
        parts = cond.split()
        if len(parts) == 5:                     # a <= f <= b
            tests.append((parts[2], int(parts[0]), int(parts[4])))
        elif len(parts) == 3:                   # f < c
            tests.append((parts[0], -10**18, int(parts[2]) - 1))
        else:                                   # f=v
            field, value = cond.split("=")
            tests.append((field, int(value), int(value)))
    value = count = 0
    for row in rows:
        if all(lo <= row[f] <= hi for f, lo, hi in tests):
            value += row[measure]
            count += 1
    return {"value": value, "count": count}


@pytest.mark.parametrize("pql", QUERIES)
def test_the_reference_agrees_with_a_per_row_loop(bench, tiny, pql):
    rows, table = tiny
    assert table.answer(bench.ref.parse(pql)) == _loop_answer(pql, rows)


def test_the_reference_is_not_trivial(bench, tiny):
    rows, table = tiny
    answers = [table.answer(bench.ref.parse(p)) for p in QUERIES]
    assert sum(a["count"] > 0 for a in answers) > 200
    assert len({a["value"] for a in answers}) > 200


def test_the_reference_reads_only_its_grammar(bench):
    parse = bench.ref.parse
    assert parse("Sum(Intersect(Row(a=3), Row(1 <= b <= 4), Row(c < 9)), "
                 "field=m)") == ("m", (("a", 3, 3), ("b", 1, 4),
                                       ("c", None, 8)))
    for bad in ("Count(Row(a=3))", "Sum(Row(a=3), field=m)",
                "Sum(Intersect(Row(a > 3)), field=m)",
                "Sum(Intersect(Row(a <= 3)), field=m)"):
        with pytest.raises(ValueError):
            parse(bad)


def test_a_sum_off_by_one_fails_the_comparison(bench, tiny):
    _, table = tiny
    want = table.answer(bench.ref.parse(QUERIES[0]))
    for key in ("value", "count"):
        bad = dict(want, **{key: want[key] + 1})
        record = [0, "query", 1.0, 2.0, 200, bad, 0.0, want, QUERIES[0]]
        assert not bench.stats.ok(record)
    record = [0, "query", 1.0, 2.0, 200, dict(want), 0.0, want, QUERIES[0]]
    assert bench.stats.ok(record)


# ------------------------------------- the loader against import_values


ROWS_A_SHARD = 1500
INT_QUERIES = [
    "Sum(field={f})", "Min(field={f})", "Max(field={f})",
    "Sum(Row(lo_year=1995), field={f})", "Max(Row(lo_year=1993), field={f})",
    "Count(Row(3 <= {f} <= 7))", "Count(Row({f} < 5))",
    "Count(Row({f} > 20))", "Count(Row({f} == 0))",
    "Count(Row({f} >= 40))", "Count(Row({f} != 4))"]


@pytest.fixture(scope="module")
def twins(bench, tmp_path_factory):
    """Two indexes over the same three shards of 1,500 rows: `blobs`
    holds the loader's roaring blobs, `values` the same set-field blobs
    and the int fields sent through `import_values`."""
    from tests.harness import ServerHarness

    cfg = dict(bench.config, shards=3)
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("ssbtwins")))
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(bench.ssb, "rows_in",
                          lambda cfg, shard: ROWS_A_SHARD)
            _load_twins(h, bench, cfg)
        h.cfg = cfg
        yield h
    finally:
        h.close()


def _load_twins(h, bench, cfg):
    for index in ("blobs", "values"):
        bench.ssb.create_schema(h.client, dict(cfg, index=index))
    sent, acked = _send(h, bench, cfg, "blobs", range(3))
    assert sent == acked > 3 * ROWS_A_SHARD * 6
    _send(h, bench, cfg, "values", range(3), int_fields=False)
    for shard in range(3):
        columns = bench.ssb.shard_columns(cfg, SEED, shard)
        cols = shard * cfg["shard_width"] + np.arange(ROWS_A_SHARD)
        for field in cfg["int_fields"]:
            h.client.import_values("values", field, cols.tolist(),
                                   columns[field].tolist())


def test_the_loaders_bsi_views_are_bit_identical(twins):
    h, cfg = twins, twins.cfg
    for field, spec in cfg["int_fields"].items():
        views = [h.holder.index(index).field(field).view("bsig_" + field)
                 for index in ("blobs", "values")]
        for shard in range(3):
            frags = [v.fragment(shard) for v in views]
            for row in range(spec["bit_depth"] + 2):
                one, other = (np.asarray(f.row_plane(row)) for f in frags)
                assert np.array_equal(one, other), (field, shard, row)
            assert sorted(frags[0].row_ids()) == sorted(frags[1].row_ids())


@pytest.mark.parametrize("field", ["lo_discount", "lo_quantity",
                                   "lo_disc_revenue"])
@pytest.mark.parametrize("query", INT_QUERIES)
def test_the_loaders_values_answer_as_import_values(twins, field, query):
    pql = query.format(f=field)
    got = _ask(twins, "blobs", pql)
    assert got == _ask(twins, "values", pql)
    if query == "Sum(field={f})":
        assert got["count"] == 3 * ROWS_A_SHARD


# ---------------------------------------------------- the server at 8 shards


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """One in-process server holding the 8-shard index, every fragment
    built and sent as the benchmark's loader builds and sends it, and the
    reference's answer to each of the 248 distinct queries."""
    from tests.harness import ServerHarness

    cfg = bench.config
    h = ServerHarness(data_dir=str(tmp_path_factory.mktemp("ssb")))
    try:
        bench.ssb.create_schema(h.client, cfg)
        sent, acknowledged = _send(h, bench, cfg, cfg["index"],
                                   range(SHARDS))
        assert sent == acknowledged > 6 * SHARDS * cfg["shard_width"]
        h.expected = bench.ssb.expected(cfg, SEED, bench.pqls)
        h.index = cfg["index"]
        yield h
    finally:
        h.close()


@pytest.mark.parametrize("via", ["http", "executor"])
@pytest.mark.parametrize("pql", QUERIES)
def test_every_query_equals_the_reference(served, pql, via):
    if via == "http":
        got = _ask(served, served.index, pql)
    else:
        result, = served.api.executor.execute(served.index, pql)
        got = result.to_json()
    assert got == served.expected[pql]
    assert got["count"] > 0


def test_the_counters_see_the_sums(served):
    """Every stack resident after one pass: a second pass only hits.
    A Sum is four launches: its two range conditions' masks, the filter
    and the sum."""
    for pql in QUERIES:
        _ask(served, served.index, pql)
    before = _vars(served)["stacked"]
    for pql in QUERIES:
        assert _ask(served, served.index, pql) == served.expected[pql]
    after = _vars(served)["stacked"]

    def delta(key):
        return after[key] - before[key]

    assert delta("sum_calls") == len(QUERIES)
    assert delta("sum_dispatches") == 4 * len(QUERIES)
    assert delta("dispatches") == 4 * len(QUERIES)
    assert delta("misses") == delta("evictions") == 0
    assert after["stack_bytes"] <= after["stack_budget_bytes"]


def test_a_profiled_sum_splits_into_conditions_filter_and_sum(served):
    pql, = [q for q in QUERIES if "lo_weeknum=12)" in q
            and "Row(8 <=" in q]
    reply = served.client.query(served.index, pql, profile=True)
    assert reply["results"][0] == served.expected[pql]
    found = []
    todo = [(reply["profile"]["spans"], None)]
    while todo:
        node, parent = todo.pop()
        found.append((node["name"], parent, node.get("tags", {})))
        todo += [(c, node["name"]) for c in node.get("children", [])]
    conditions = [t for name, _, t in found if name == "stack.condition"]
    assert sorted((t["field"], t["plan"]) for t in conditions) \
        == [("lo_discount", "between"), ("lo_quantity", "between")]
    kernels = sorted(t["op"] for name, parent, t in found
                     if name == "stacked.kernel")
    assert kernels == ["bsi_condition", "bsi_condition", "filter", "sum"]
    # each condition's stack lookup and mask launch lie under its span
    under = {name for name, parent, _ in found
             if parent == "stack.condition"}
    assert {"stack.lookup", "dispatch.lock_wait",
            "stacked.kernel"} <= under
    spans = _vars(served)["spans"]
    assert spans["stack.condition"]["count"] >= 2


@pytest.mark.parametrize("fault", SUM_FAULTS)
def test_a_broken_sum_reads_incorrect(fault):
    """`run.py`'s own comparison catches a wrong Sum: the cell's faults
    (`harness/sum_faults.py`), at 8 shards."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/harness/sum_faults.py", "--workload",
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


def test_run_rehearses_the_cell():
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
    # the roofline needs the chip's peak: nothing on the host
    assert set(NEW_METRICS) - readable == {"kernel.roofline_share.bsi"}
    assert set(LISTED) <= readable


# ------------------------------------------------------------- the readers


def _context(bench, before=None, after=None, profiles=(), trace=None,
             queries=(), device=None, config=None):
    run = types.SimpleNamespace(
        cell={}, config=config or {}, spec={},
        device=device or {"platform": "cpu"}, opened=0.0, closed=40.0,
        t0=-80.0, window=[], profiles=list(profiles),
        before={"vars": {"stacked": before or {}}},
        after={"vars": {"stacked": after or {}}}, trace=trace,
        traced_queries=list(queries))
    return bench.cell.Context(run)


def test_reader_of_the_sum_launches(bench):
    read = bench.manifest.reader("exec.launches_per_call.sum")
    before = {"sum_calls": 10, "sum_dispatches": 40}
    after = {"sum_calls": 510, "sum_dispatches": 2040}
    assert read(_context(bench, before, after)) == pytest.approx(4.0)
    # a parent commit has neither counter: nothing, and no error
    parent = {"dispatches": 5, "pairwise_calls": 2}
    assert read(_context(bench, parent, parent)) is None
    assert read(_context(bench, before, before)) is None


def test_reader_of_the_sum_span(bench):
    read = bench.manifest.reader("path.call_ms.sum")
    profiles = [{"executor.executeSum": [s, 0.0], "api.Query": [1.0, 0.1]}
                for s in (0.004, 0.012, 0.008)]
    profiles.append({"executor.executeCount": [9.0, 9.0]})
    assert read(_context(bench, profiles=profiles)) == pytest.approx(8.0)
    assert read(_context(bench)) is None


def test_the_roofline_bytes_are_the_hand_count(bench):
    full = bench.full
    plane = 172 * 32768 * 4
    # measure 27 + 2, discount 4 + 2, quantity 6 + 2, and the set rows
    hand = {"q1_1": 29 + 6 + 8 + 1, "q1_2": 29 + 6 + 8 + 1,
            "q1_3": 29 + 6 + 8 + 2}
    by_template = {
        op["name"]: op["pql"].format(
            **{k: v["choice"][0] for k, v in op["args"].items()})
        for op in bench.spec["operations"]}
    assert sorted(by_template) == sorted(hand)
    for name, pql in by_template.items():
        assert bench.roofline_bsi.query_bytes(pql, full) \
            == hand[name] * plane, name
    # 0.99 and 1.01 GB a query: 1.21 and 1.24 ms at the v5e's 819 GB/s
    assert round(44 * plane / 819e9 * 1e3, 2) == 1.21
    assert round(45 * plane / 819e9 * 1e3, 2) == 1.24


def test_reader_of_the_bsi_roofline(bench):
    full = bench.full
    q1_1 = [q for q in QUERIES if "lo_year=" in q and "weeknum" not in q][0]
    q1_3 = [q for q in QUERIES if "lo_weeknum" in q][0]
    plane = 172 * 32768 * 4
    read = bench.manifest.reader("kernel.roofline_share.bsi")
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    least = (44 + 45) * plane / 819e9
    trace = {"busy_s": least * 4}
    got = read(_context(bench, trace=trace, queries=[q1_1, q1_3],
                        device=tpu, config=full))
    assert got == pytest.approx(25.0)
    # nothing on the host CPU, without a trace, or without a query in it
    assert read(_context(bench, trace=trace, queries=[q1_1],
                         config=full)) is None
    assert read(_context(bench, queries=[q1_1], device=tpu,
                         config=full)) is None
    assert read(_context(bench, trace=trace, device=tpu,
                         config=full)) is None

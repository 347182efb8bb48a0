"""The yardstick's arithmetic: manifest rules, the window's statistics,
the traffic generator, the oracle, the roofline's bytes and the trace
reduction."""

import gzip
import json
import os
import re

import numpy as np
import pytest

from harness import manifest, oracle, reduce_trace, roofline, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# ---------------------------------------------------------------- manifest


@pytest.fixture(scope="module")
def bench():
    return manifest.load()


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names))
    for cell in bench["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert len(cell["why"]) <= 200 and cell["chips"] in (1, 4)
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_named_file_exists(bench):
    cells = {c["name"] for c in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        cfg = manifest.config(bench, cell["config"])
        assert cfg["chips"] == cell["chips"]
        assert os.path.exists(os.path.join(
            manifest.BENCH, "data", cfg["data_module"] + ".py"))
        assert traffic.load(manifest.traffic_path(cell["traffic"]))
    assert {c["config"] for c in bench["workloads"]} == set(configs)
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert callable(manifest.reader(m["name"])), m["name"]
            assert set(m.get("workloads", cells)) <= cells


def test_cells_report_what_their_metrics_move(bench):
    reports = {c["name"]: {m["name"] for m in manifest.metrics(
        bench, "end_to_end", c["name"])} for c in bench["workloads"]}
    for cell, have in reports.items():
        assert "setup_s" in have and len(have) >= 2, cell
        assert manifest.metrics(bench, "per_layer", cell), cell
    for m in bench["per_layer"]:
        for cell in m.get("workloads", reports):
            assert m["moves"] in reports[cell], (m["name"], cell)
    # a cell reads a quantity once, under one name
    for cell in reports:
        files = [manifest.reader_path(m["name"]) for m in manifest.metrics(
            bench, "per_layer", cell)]
        assert len(files) == len(set(files)), cell
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


# ------------------------------------------------------------------- stats


def rec(kind, sent, done, status=200, answer=7, expect=7, client=0):
    return [client, kind, sent, done, status, answer, 0.001, expect, "q"]


def test_percentile_and_rate():
    assert stats.percentile([], 50) is None
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(
        float(np.percentile(xs, 95)))
    records = [rec("query", t, t + 0.004) for t in np.arange(0, 10, 0.01)]
    window = stats.in_window(records, 2.0, 7.0)
    assert stats.rate(window, "query", 5.0) == pytest.approx(100, abs=0.5)
    assert stats.percentile(stats.latencies_ms(window, "query"),
                            50) == pytest.approx(4.0)


def test_a_stall_moves_the_tail_not_the_median():
    """One 300 ms stall behind which 3 of 1,000 requests wait: the p95
    stays, a p99.9 would not; the rate counts every request once."""
    records = [rec("query", i * 0.005, i * 0.005 + 0.004)
               for i in range(1000)]
    for i in (400, 401, 402):
        records[i] = rec("query", i * 0.005, i * 0.005 + 0.3)
    window = stats.in_window(records, 0.0, 10.0)
    lat = stats.latencies_ms(window, "query")
    assert len(lat) == 1000
    assert stats.percentile(lat, 50) == pytest.approx(4.0)
    assert stats.percentile(lat, 95) == pytest.approx(4.0)
    assert stats.percentile(lat, 99.9) > 100
    # a request answered after the window closed is not in it
    assert len(stats.in_window(records, 0.0, 2.0)) == 400


def test_failures_and_wrong_answers():
    records = [rec("query", 0, 1), rec("query", 0, 1, answer=8),
               rec("query", 0, 1, status=503, answer=None),
               rec("import_bits", 0, 1, answer=None, expect=None),
               rec("readback", 0, 1, answer=3, expect=4)]
    s = stats.summary(records, 0, 2)
    assert (s["attempted"], s["failed"]) == (5, 3)
    assert stats.wrong(records) == (2, 1)
    assert stats.latencies_ms(records, "query") == [1000.0]


# ----------------------------------------------------------------- traffic


CFG = {"index": "north", "shards": 8, "shard_width": 1 << 20,
       "fields": ["f", "g"], "rows": [1, 2], "word_density": 0.05}


@pytest.mark.parametrize("name", ["read-c32", "read-c1", "rw95-c32"])
def test_traffic_is_drawn_from_the_seed(name):
    spec = traffic.load(manifest.traffic_path(name))

    def ops(seed, client, n=400):
        draw = traffic.ClientDraw(spec, CFG, seed, client)
        return [draw.draw() for _ in range(n)]

    assert ops(2**31 + 11, 3) == ops(2**31 + 11, 3)
    assert ops(2**31 + 11, 3) != ops(2**31 + 12, 3)
    queries = set(traffic.distinct_queries(spec))
    assert len(queries) == 16
    sent = [op for c in range(spec["clients"]) for op in ops(5, c, 100)]
    assert {op["pql"] for op in sent if op["kind"] == "query"} <= queries
    writes = [op for op in sent if op["kind"] == "import_bits"]
    share = len(writes) / len(sent)
    want = sum(op["weight"] for op in spec["operations"]
               if op["kind"] == "import_bits")
    assert abs(share - want) < 0.03
    for op in writes:
        assert {c // CFG["shard_width"] for c in op["columns"]} == {
            op["shard"]}
        assert op["row"] >= 100 and len(op["columns"]) == 64


def test_zipf_arguments():
    spec = {"clients": 1, "operations": [
        {"name": "r", "kind": "query", "weight": 1,
         "pql": "Count(Row(f={i}))",
         "args": {"i": {"zipf": {"n": 4, "s": 0.99, "base": 1}}}}]}
    draw = traffic.ClientDraw(spec, CFG, 1, 0)
    seen = [draw.draw()["pql"] for _ in range(2000)]
    counts = [seen.count(f"Count(Row(f={i}))") for i in (1, 2, 3, 4)]
    assert sum(counts) == 2000 and counts == sorted(counts, reverse=True)
    assert traffic.distinct_queries(spec) == [
        f"Count(Row(f={i}))" for i in (1, 2, 3, 4)]


# ------------------------------------------------------ oracle and roofline


def test_oracle_counts():
    rng = np.random.default_rng(0)
    planes = {(f, r): rng.integers(0, 1 << 32, (3, 64), dtype=np.uint32)
              for f in "fg" for r in (1, 2)}
    bits = {k: np.unpackbits(v.view(np.uint8)) for k, v in planes.items()}
    f1, f2, g1 = bits["f", 1], bits["f", 2], bits["g", 1]
    assert oracle.evaluate("Count(Row(f=1))", planes) == int(f1.sum())
    assert oracle.evaluate("Count(Difference(Row(f=2), Row(g=1)))",
                           planes) == int((f2 & ~g1 & 1).sum())
    assert oracle.evaluate(
        "Count(Intersect(Union(Row(f=1), Row(f=2)), Row(g=1)))",
        planes) == int(((f1 | f2) & g1).sum())
    assert oracle.evaluate("Count(Xor(Row(f=1),Row(g=1)))",
                           planes) == int((f1 ^ g1).sum())
    with pytest.raises(ValueError):
        oracle.evaluate("TopN(f, n=3)", planes)


def test_roofline_bytes():
    cfg = {"shards": 954, "shard_width": 1 << 20}
    two = "Count(Intersect(Row(f=1), Row(g=2)))"
    three = "Count(Intersect(Union(Row(f=1), Row(f=2)), Row(g=1)))"
    assert roofline.query_bytes(two, cfg) == 2 * 954 * 32768 * 4
    assert roofline.query_bytes(three, cfg) == 3 * 954 * 32768 * 4
    least = roofline.least_seconds([two], cfg, "TPU v5 lite", 1)
    assert least == pytest.approx(250_085_376 / 819e9)
    assert roofline.least_seconds([two], cfg, "TPU v5 lite", 4) == \
        pytest.approx(least / 4)
    with pytest.raises(LookupError):
        roofline.least_seconds([two], cfg, "TPU v9", 1)


# --------------------------------------------------------- trace reduction


def test_reduce_synthetic_trace():
    ms = 1_000_000
    events = {
        "devices": {"/device:TPU:0": [["fusion.1", 0, 2 * ms],
                                      ["fusion.1", 1 * ms, 2 * ms],
                                      ["copy.2", 10 * ms, 1 * ms]]},
        "host": [["outer", 0, 20 * ms], ["np.asarray", 3 * ms, 7 * ms],
                 ["PjitFunction(fn)", 11 * ms, 8 * ms]],
    }
    out = reduce_trace.reduce(events)
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.004)      # 0-3 and 10-11 ms
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    gaps = dict(out["idle_gaps"])
    assert gaps["np.asarray"] == pytest.approx(0.007)
    assert gaps["PjitFunction(fn)"] == pytest.approx(0.009)
    assert reduce_trace.reduce({"devices": {}, "host": []}) is None


def test_a_polling_wait_still_names_its_gap():
    """A wait that polls in thousands of short events (`ReadSyncFlag` on
    one client) names the gap it covers, however many polls came first."""
    us = 1_000
    polls = [["poll", 100 * us + i * us // 2, us // 4] for i in range(4000)]
    events = {
        "devices": {"/device:TPU:0": [["fusion.1", 0, 100 * us],
                                      ["fusion.1", 5000 * us, 100 * us]]},
        "host": [["np.asarray", 50 * us, 3000 * us]] + polls,
    }
    gaps = dict(reduce_trace.reduce(events)["idle_gaps"])
    assert gaps == {"np.asarray": pytest.approx(0.0049)}


def test_reduce_clips_to_the_marked_span():
    """The profiler records more than the span the queries are counted in:
    with the harness's mark, busy time, window, operations and gaps are of
    the marked span alone."""
    ms = 1_000_000
    events = {
        "devices": {"/device:TPU:0": [["fusion.1", 0, 2 * ms],
                                      ["fusion.1", 4 * ms, 2 * ms],
                                      ["fusion.1", 9 * ms, 2 * ms],
                                      ["copy.2", 14 * ms, 3 * ms]],
                    "/device:TPU:1": [["fusion.1", 20 * ms, 1 * ms]]},
        "host": [[reduce_trace.SPAN_MARK, 5 * ms, 10 * ms],
                 ["np.asarray", 0, 30 * ms]],
    }
    out = reduce_trace.reduce(events)
    assert out["window_s"] == pytest.approx(0.010)
    # 5-6, 9-11 and 14-15 ms; the second device did nothing in the span
    assert out["busy_s"] == pytest.approx(0.004)
    assert list(out["busy_by_device"]) == ["/device:TPU:0"]
    assert dict(out["device_ops"]) == {
        "fusion.1": pytest.approx(0.003), "copy.2": pytest.approx(0.001)}
    assert dict(out["idle_gaps"]) == {"np.asarray": pytest.approx(0.006)}
    events["devices"] = {"/device:TPU:1": events["devices"]["/device:TPU:1"]}
    assert reduce_trace.reduce(events) is None


def test_reduce_recorded_trace():
    """A slice of a `--trace 1` run on a TPU v5e, kept by --keep-trace."""
    path = os.path.join(HERE, "recorded_trace.events.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    with open(os.path.join(HERE, "recorded_trace.expect.json")) as f:
        want = json.load(f)
    out = reduce_trace.reduce(events)
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    assert [n for n, _ in out["device_ops"]] == [
        n for n, _ in want["device_ops"]]
    assert out["idle_gaps"][0][0] == want["idle_gaps"][0][0]
    # by hand: busy time is the sum of the ops where none overlap
    ops = [e for v in events["devices"].values() for e in v]
    assert out["busy_s"] <= sum(e[2] for e in ops) / 1e9 + 1e-12


def test_extract_reads_a_trace_made_here(tmp_path):
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((256, 256))
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    float(fn(x))
    with jax.profiler.TraceAnnotation(reduce_trace.SPAN_MARK):
        for _ in range(3):
            float(fn(x))
    float(fn(x))
    jax.profiler.stop_trace()
    events = reduce_trace.extract(reduce_trace.find_xplane(str(tmp_path)))
    out = reduce_trace.reduce(events)
    assert out["busy_s"] > 0 and out["window_s"] > out["busy_s"]
    # the mark is found in a real trace, and it narrows the window
    unmarked = dict(events, host=[
        e for e in events["host"] if e[0] != reduce_trace.SPAN_MARK])
    assert len(unmarked["host"]) == len(events["host"]) - 1
    assert out["window_s"] < reduce_trace.reduce(unmarked)["window_s"]
    assert "PLANE" in reduce_trace.describe(
        reduce_trace.find_xplane(str(tmp_path)))

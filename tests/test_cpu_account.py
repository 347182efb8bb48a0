"""The server's CPU, accounted for: the edge span every profiled or
traced request opens (its root, with `http.parse` and `http.reply` under
it and the query's profile beside them), the stage spans of an import,
`/debug/vars` `threads`, and the benchmark's readers of them."""

import importlib.util
import json
import os
import sys
import threading
import time
import types

import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import profile as profile_mod
from pilosa_tpu.utils import threadcpu, tracing

from tests.harness import ServerHarness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
QUERY_ROUTE = "/index/(?P<index>[^/]+)/query"
IMPORT_ROUTE = "/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import"
QUERY_EDGE = "http.POST " + QUERY_ROUTE
IMPORT_EDGE = "http.POST " + IMPORT_ROUTE
READ_CELLS = ["seg1b-read-c32", "seg1b-read-c1", "seg1b-x4-read-c32",
              "seg1b-r8-read-c32", "taxi250m-rank-c32", "ssb40-flight1-c32"]
RW_CELL = "seg1b-rw-c32"


@pytest.fixture
def published(monkeypatch):
    """Every span this process publishes, in the order they finish; every
    traced request clocked (CPU_SAMPLE 1)."""
    monkeypatch.setattr(profile_mod, "CPU_SAMPLE", 1)
    spans = []
    count = tracing.count_span

    def keep(span):
        spans.append(span)
        count(span)

    monkeypatch.setattr(tracing, "count_span", keep)
    return spans


def _wait_for(spans, name, n=1):
    """The edge ends after the reply's last byte, so a client can hold the
    reply before the server has published it."""
    deadline = time.monotonic() + 10
    while sum(s.name == name for s in spans) < n:
        assert time.monotonic() < deadline, f"no {name} span"
        time.sleep(0.005)
    return [s for s in spans if s.name == name]


def _seed(h, index="ca", n_shards=3):
    h.api.create_index(index)
    h.api.create_field(index, "f")
    h.api.import_bits(index, "f", [1] * n_shards,
                      [s * SHARD_WIDTH + 5 for s in range(n_shards)])


def test_profiled_query_edge_is_the_parent_of_query(tmp_path, published):
    """The edge is the request's root: `http.parse`, `query` and
    `http.reply` are its same-thread children in that order, share its
    trace, and its own time and CPU are its own less theirs. The reply's
    profile tree is the query's, as before."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        _seed(h)
        reply = h.client.query("ca", "Count(Row(f=1))", profile=True)
        assert reply["results"] == [3]
        edge, = _wait_for(published, QUERY_EDGE)
    finally:
        h.close()
    assert reply["profile"]["spans"]["name"] == "query"
    assert not any(c["name"].startswith("http.")
                   for c in reply["profile"]["spans"]["children"])
    kids = [s for s in published if s.parent_id == edge.span_id]
    assert [s.name for s in kids] == ["http.parse", "query", "http.reply"]
    query = kids[1]
    assert reply["profile"]["traceID"] == query.trace_id == edge.trace_id
    assert edge.parent_id is None and edge.tags["status"] == 200
    for s in kids:
        assert s._tid == edge._tid and s.cpu_weight == edge.cpu_weight == 1
        assert s.start >= edge.start
    assert edge.self_time == pytest.approx(
        edge.duration - sum(s.duration for s in kids), abs=1e-9)
    assert edge.self_cpu == pytest.approx(
        max(0.0, edge.cpu - sum(s.cpu for s in kids)), abs=1e-9)
    assert edge.cpu >= query.cpu and edge.self_cpu <= edge.cpu - query.cpu
    # the profile's root was not a root of its own
    assert query.parent_id == edge.span_id


def test_edge_names_by_route_not_by_path(tmp_path, published):
    """Queries on two indexes give one edge name: no name in
    /debug/vars `spans` is built from a raw request path."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        for index in ("alpha", "beta"):
            _seed(h, index)
            h.client.query(index, "Count(Row(f=1))", profile=True)
        _wait_for(published, QUERY_EDGE, 2)
        names = set(h.client._request("GET", "/debug/vars")["spans"])
    finally:
        h.close()
    assert QUERY_EDGE in names
    assert not [n for n in names if "alpha" in n or "beta" in n]
    assert {s.name for s in published if s.name.startswith("http.")} \
        == {QUERY_EDGE, "http.parse", "http.reply"}


def test_unprofiled_requests_create_no_span(tmp_path, monkeypatch):
    """No profile, no recording tracer, no incoming trace: a query, a
    GET and an unmatched path allocate no Span at all."""
    tracing.set_tracer(None)
    h = ServerHarness(data_dir=str(tmp_path))
    made = []
    real_init = tracing.Span.__init__

    def counting_init(self, *a, **k):
        made.append(a[0])
        real_init(self, *a, **k)

    try:
        _seed(h)
        h.client.query("ca", "Count(Row(f=1))")
        monkeypatch.setattr(tracing.Span, "__init__", counting_init)
        for _ in range(3):
            assert h.client.query("ca", "Count(Row(f=1))")["results"] == [3]
        h.client._request("GET", "/status")
        with pytest.raises(Exception):
            h.client._request("GET", "/no/such/path")
    finally:
        monkeypatch.undo()
        h.close()
    assert made == []


def test_import_stage_spans_nest_under_the_edge(tmp_path, published):
    """An import opens its edge unprofiled; `oplog.append`, `field.import`
    and `oplog.applied` run inside it on its thread, in that order, and
    reach /debug/vars `spans`."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        h.client.create_index("im")
        h.client.create_field("im", "f")
        before = h.client._request("GET", "/debug/vars")["spans"]
        h.client.import_bits("im", "f", [7, 7], [1, SHARD_WIDTH + 1])
        edge, = _wait_for(published, IMPORT_EDGE)
        after = h.client._request("GET", "/debug/vars")["spans"]
    finally:
        h.close()
    stages = [s for s in published if s.parent_id == edge.span_id]
    assert [s.name for s in stages] == ["http.parse", "oplog.append",
                                        "field.import", "oplog.applied",
                                        "http.reply"]
    end = edge.start + edge.duration
    for s in stages:
        assert s.trace_id == edge.trace_id and s._tid == edge._tid
        assert edge.start <= s.start and s.start + s.duration <= end + 1e-3
    assert sum(s.duration for s in stages) <= edge.duration
    for name in (IMPORT_EDGE, "oplog.append", "field.import",
                 "oplog.applied"):
        gained = after[name]["count"] - before.get(name, {"count": 0})["count"]
        assert gained == 1, name
        assert after[name]["seconds"] > 0


def test_debug_vars_threads_by_group(tmp_path):
    """A busy named thread shows in its own group (its number taken off);
    the handler threads count under `request`; the groups with `exited`
    sum to the process's CPU, and the live ones alone exceed it by at
    most a tick a thread."""
    stop = threading.Event()
    spun = threading.Event()

    def spin():
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.3:
            pass
        spun.set()
        stop.wait(30)

    busy = threading.Thread(target=spin, name="busy-spinner-7", daemon=True)
    busy.start()
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        assert spun.wait(30)
        out = h.client._request("GET", "/debug/vars")
    finally:
        stop.set()
        h.close()
    threads = out["threads"]
    assert threads["busy-spinner"]["threads"] == 1
    assert threads["busy-spinner"]["cpu_seconds"] >= 0.2
    assert threads["request"]["threads"] >= 1
    assert {"runtime", "exited", "MainThread"} <= set(threads)
    assert set(threads["runtime"]) == {"cpu_seconds", "threads", "comm"}
    assert sum(threads["runtime"]["comm"].values()) == pytest.approx(
        threads["runtime"]["cpu_seconds"])
    total = sum(g["cpu_seconds"] for g in threads.values())
    assert total == pytest.approx(out["process"]["cpu_seconds"], abs=1e-9)
    n = sum(g["threads"] for g in threads.values())
    assert threads["exited"]["cpu_seconds"] >= -n / threadcpu._TICK


def test_threads_section_absent_without_proc(tmp_path, monkeypatch):
    monkeypatch.setattr(threadcpu, "_TASKS", str(tmp_path / "no-proc"))
    h = ServerHarness(data_dir=str(tmp_path / "data"))
    try:
        out = h.client._request("GET", "/debug/vars")
    finally:
        h.close()
    assert "threads" not in out
    assert out["process"]["cpu_seconds"] > 0


@pytest.mark.parametrize("name, group", [
    ("pilosa-workpool-3", "request"), ("pilosa-request", "request"),
    ("cache-flush", "cache-flush"), ("Thread-12 (serve)", "Thread"),
    ("ingest-merge", "ingest-merge"), ("MainThread", "MainThread")])
def test_thread_group_names(name, group):
    got = threadcpu.group_name(name)
    assert (got if got not in threadcpu.REQUEST_GROUPS else "request") \
        == group


def test_dispatch_phase_histogram_is_gone(tmp_path):
    """`dispatch_phase_seconds` is in neither /metrics nor /debug/vars
    `timings`; /debug/dispatch still has every phase."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        _seed(h)
        h.client.create_field("ca", "g")
        h.api.import_bits("ca", "g", [1, 1], [5, SHARD_WIDTH + 5])
        h.client.query("ca", "Count(Intersect(Row(f=1), Row(g=1)))")
        metrics = h.client._request("GET", "/metrics")
        timings = h.client._request("GET", "/debug/vars")["timings"]
        phases = h.client._request("GET", "/debug/dispatch")
    finally:
        h.close()
    text = metrics.decode() if isinstance(metrics, bytes) else str(metrics)
    assert "dispatch_phase_seconds" not in text
    assert not [k for k in timings if k.startswith("dispatch_phase")]
    assert phases["phases"] and all(
        "lock_wait" in fam for fam in phases["phases"].values())


# ------------------------------------------------------- benchmark readers


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        from harness import cell, manifest
        yield types.SimpleNamespace(cell=cell, manifest=manifest)
    finally:
        sys.path.remove(BENCH)


def _context(bench, before=None, after=None):
    run = types.SimpleNamespace(
        cell={}, config={}, spec={}, device={}, opened=0.0, closed=40.0,
        t0=-50.0, window=[], profiles=[],
        before={"vars": before or {}}, after={"vars": after or {}},
        trace=None, traced_queries=[])
    return bench.cell.Context(run)


def _row(count, seconds, cpu):
    return {"count": count, "seconds": seconds, "self_seconds": seconds,
            "cpu_seconds": cpu, "self_cpu_seconds": cpu}


def _made_up(bench):
    """A window of 100 profiled queries (120 answered) and 10 imports over
    counters that were already running; every number is by hand."""
    key = f"http_request_seconds{{method=POST,route={QUERY_ROUTE},status=200}}"

    def vars_(k):
        return {
            "spans": {QUERY_EDGE: _row(10 + 100 * k, 1.0, 2.0 + 0.5 * k),
                      "query": _row(10 + 100 * k, 1.0, 1.0 + 0.3 * k),
                      "api.Query": _row(10 + 100 * k, 1.0, 1.0),
                      IMPORT_EDGE: _row(5 + 10 * k, 1.0, 1.0 + 0.04 * k),
                      "field.import": _row(5 + 10 * k, 1.0 + 0.02 * k, 0.0)},
            "threads": {"request": {"cpu_seconds": 10 + 0.36 * k},
                        "runtime": {"cpu_seconds": 5 + 0.12 * k,
                                    "comm": {"tpu": 5 + 0.12 * k}},
                        "cache-flush": {"cpu_seconds": 1 + 0.024 * k},
                        "MainThread": {"cpu_seconds": 2 + 0.012 * k},
                        "exited": {"cpu_seconds": 3 + 1.0 * k}},
            "timings": {key: {"count": 20 + 120 * k, "sum": 1.0}}}
    before, after = vars_(0), vars_(1)
    after["threads"]["fsync-interval"] = {"cpu_seconds": 0.012}
    return _context(bench, before, after)


EXPECT = {
    "edge.cpu_ms": (0.5 - 0.3) / 100 * 1e3,
    "import.cpu_ms": 0.04 / 10 * 1e3,
    "storage.apply_ms": 0.02 / 10 * 1e3,
    "proc.request_cpu_ms": 0.36 / 120 * 1e3,
    "proc.runtime_cpu_ms": 0.12 / 120 * 1e3,
    "proc.background_cpu_ms": (0.024 + 0.012 + 0.012) / 120 * 1e3,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_of_a_cpu_account_metric(bench, name):
    """None on a program without the span or section (a parent commit),
    else the number by hand; a `.rw` name is read by the same file."""
    read = bench.manifest.reader(name)
    assert read(_context(bench)) is None
    assert read(_made_up(bench)) == pytest.approx(EXPECT[name])
    assert bench.manifest.reader_path(name + ".rw") \
        == bench.manifest.reader_path(name)


def test_cpu_account_metrics_are_entered_for_their_cells(bench):
    per_layer = {m["name"]: m for m in bench.manifest.load()["per_layer"]}
    for name in ("edge.cpu_ms", "proc.request_cpu_ms", "proc.runtime_cpu_ms",
                 "proc.background_cpu_ms"):
        assert per_layer[name]["workloads"] == READ_CELLS
        assert per_layer[name]["moves"] == "query_qps"
        assert per_layer[name + ".rw"]["workloads"] == [RW_CELL]
        assert per_layer[name + ".rw"]["moves"] == "write_ack_p50_ms"
    for name in ("import.cpu_ms", "storage.apply_ms"):
        assert per_layer[name]["workloads"] == [RW_CELL]
        assert per_layer[name]["layer"] == "storage"
    spec = importlib.util.spec_from_file_location(
        "benchmark_test_harness_cpu",
        os.path.join(BENCH, "tests", "test_harness.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    for rule in ("test_names_and_units", "test_every_named_file_exists",
                 "test_cells_report_what_their_metrics_move"):
        getattr(module, rule)(bench.manifest.load())


def test_span_sums_take_the_clocks_reads_off(monkeypatch):
    """A clocked span's CPU enters the per-name sums less the reads of
    the clock made inside it — its own, each same-thread descendant's two
    in `cpu`, one a direct child in `self_cpu` — weighed like the span:
    the unclocked requests it stands for made no such reads."""
    monkeypatch.setattr(tracing, "_clock_cost", 0.001)
    tracing.set_tracer(tracing.InMemoryTracer())
    try:
        zero = {"count": 0, "cpu_seconds": 0.0, "self_cpu_seconds": 0.0}
        names = ("rd.parent", "rd.a", "rd.b", "rd.c")
        before = {n: tracing.span_stats().get(n, zero) for n in names}
        with tracing.start_span("rd.parent") as parent:
            with tracing.start_span("rd.a") as a:
                pass
            with tracing.start_span("rd.b") as b:
                with tracing.start_span("rd.c") as c:
                    pass
        stats = tracing.span_stats()
    finally:
        tracing.set_tracer(None)
    assert (parent._reads, parent._nkids) == (6, 2)
    assert (b._reads, b._nkids) == (2, 1)
    assert (a._reads, a._nkids, c._reads, c._nkids) == (0, 0, 0, 0)
    for span, reads, kids in ((parent, 6, 2), (a, 0, 0), (b, 2, 1),
                              (c, 0, 0)):
        row, was = stats[span.name], before[span.name]
        assert row["count"] - was["count"] == 1
        assert row["cpu_seconds"] - was["cpu_seconds"] == pytest.approx(
            span.cpu - 0.001 * (reads + 1))
        assert row["self_cpu_seconds"] - was["self_cpu_seconds"] \
            == pytest.approx(span.self_cpu - 0.001 * (kids + 1))
    # over the tree the self sums are the root's CPU less every read
    # but the root's own outer halves
    total = sum(stats[n]["self_cpu_seconds"] - before[n]["self_cpu_seconds"]
                for n in names)
    assert total == pytest.approx(parent.cpu - 0.001 * 7)

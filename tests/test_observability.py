"""End-to-end query observability: per-query span-tree profiles
(?profile=true), the /debug/queries ring, slow-query logging with embedded
profiles, Prometheus histogram exposition, per-route request metrics, and
the runtime monitor's device gauges.

The acceptance contract (ISSUE 2): a profiled two-field GroupBy over a
multi-shard index returns a span tree whose root covers its kernel spans
and whose dispatch tags agree with the exported stacked counters, while
the nop tracer stays the zero-overhead default.
"""

import json
import re
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import profile as profile_mod
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.logger import CaptureLogger
from pilosa_tpu.utils.stats import (
    TIMING_BUCKETS,
    RuntimeMonitor,
    StatsClient,
)
from tests.harness import ServerHarness


# --------------------------------------------------------------- helpers


def _seed_groupby(h, index="gp", n_shards=3, n=300, seed=7):
    """Two set fields with bits spread across n_shards shards."""
    h.api.create_index(index)
    h.api.create_field(index, "a")
    h.api.create_field(index, "b")
    rng = np.random.default_rng(seed)
    cols = rng.choice(n_shards * SHARD_WIDTH, size=n, replace=False)
    ra = rng.integers(0, 5, size=n)
    rb = rng.integers(0, 4, size=n)
    h.api.import_bits(index, "a", ra.tolist(), cols.tolist())
    h.api.import_bits(index, "b", rb.tolist(), cols.tolist())
    return cols


def _walk(node):
    yield node
    for child in node["children"]:
        yield from _walk(child)


#: one exposition sample: name{labels} value (labels with escaped values)
_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>(?:[a-zA-Z_][a-zA-Z0-9_]*='
    r'"(?:[^"\\\n]|\\.)*",?)*)\})?'
    r' (?P<value>[-+.0-9eE]+|\+Inf|NaN)$')

_TYPE_RE = re.compile(
    r"^# TYPE (?P<family>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r" (?:counter|gauge|histogram)$")


def _parse_prometheus(text):
    """Strict line parser: every line must be a valid sample or # TYPE
    comment; returns ({(name, label_string): value}, [family names])."""
    samples = {}
    families = []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _TYPE_RE.match(line)
            assert m, f"malformed comment line: {line!r}"
            families.append(m.group("family"))
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        samples[(m.group("name"), m.group("labels") or "")] = \
            float(m.group("value"))
    return samples, families


def _histogram_series(samples, family, label_filter):
    """(sorted [(bound, cumulative)], count, sum) for one histogram
    series, matching label substrings in label_filter."""
    buckets = []
    count = total = None
    for (name, labels), value in samples.items():
        if not all(f in labels for f in label_filter):
            continue
        if name == f"{family}_bucket":
            le = re.search(r'le="([^"]*)"', labels).group(1)
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            value))
        elif name == f"{family}_count":
            count = value
        elif name == f"{family}_sum":
            total = value
    buckets.sort()
    return buckets, count, total


# ---------------------------------------------- tentpole acceptance path


def test_profile_span_tree_matches_dispatch_counters(tmp_path):
    """?profile=true on a two-field GroupBy over a multi-shard index:
    the span tree's root covers its kernel spans and the profile's
    pairwise tag equals both the exported counter delta and the number
    of pairwise kernel spans."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        cols = _seed_groupby(h)
        before = h.client._request(
            "GET", "/debug/vars")["stacked"]["pairwise_dispatches"]
        resp = h.client.query("gp", "GroupBy(Rows(a), Rows(b))",
                              profile=True)
        after = h.client._request(
            "GET", "/debug/vars")["stacked"]["pairwise_dispatches"]

        assert resp["results"], "GroupBy returned nothing"
        prof = resp["profile"]
        assert prof is not None
        assert prof["index"] == "gp"
        assert prof["query"].startswith("GroupBy")
        assert prof["duration"] > 0 and not prof["slow"]

        root = prof["spans"]
        assert root["name"] == "query"
        names = {s["name"] for s in _walk(root)}
        assert "api.Query" in names
        assert "executor.Execute" in names
        assert "executor.executeGroupBy" in names

        # root duration covers the (serialized) kernel dispatches
        kernels = [s for s in _walk(root) if s["name"] == "stacked.kernel"]
        assert kernels, "no kernel spans captured"
        assert all(s["duration"] is not None for s in kernels)
        assert root["duration"] >= sum(s["duration"] for s in kernels)

        # dispatch accounting: profile tag == exported counter delta ==
        # number of pairwise kernel spans in the tree
        pairwise = [s for s in kernels if s["tags"].get("op") == "pairwise"]
        assert after - before >= 1
        assert prof["tags"]["pairwise_dispatches"] == after - before
        assert prof["tags"]["pairwise_dispatches"] == len(pairwise)

        # counters the glossary promises (docs/architecture.md)
        tags = prof["tags"]
        assert tags["shards_touched"] == \
            len({int(c) // SHARD_WIDTH for c in cols})
        assert tags["locked_dispatches"] == len(kernels)
        assert tags["kernel_wall_seconds"] >= 0
        assert tags["dispatch_lock_wait_seconds"] >= 0
        assert tags["bytes_materialized"] >= 0
        assert tags["cache_hits"] >= 0 and tags["cache_misses"] >= 0
        # the wait for the dispatch lock is a span of its own beside
        # each kernel span (it was a tag on it)
        waits = [s for s in _walk(root) if s["name"] == "dispatch.lock_wait"]
        assert len(waits) == len(kernels)
        assert all(s["duration"] >= 0 for s in waits)
        assert tags["dispatch_lock_wait_seconds"] >= \
            sum(s["duration"] for s in waits)

        # per-op latency histograms landed in the registry behind /metrics
        text = h.client._request("GET", "/metrics").decode()
        assert 'pilosa_tpu_query_op_seconds_count{op="GroupBy"}' in text
    finally:
        h.close()


def test_profile_off_by_default(tmp_path):
    """Without ?profile=true and without long-query-time, nothing is
    profiled, nothing is retained, and the nop tracer stays installed."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        profile_mod.clear_recent()
        profile_mod.take_last()  # whatever an earlier test left here
        h.client.create_index("np")
        h.client.create_field("np", "f")
        h.client.query("np", "Set(1, f=10)")
        resp = h.client.query("np", "Count(Row(f=10))")
        assert resp["results"] == [1]
        assert "profile" not in resp
        assert profile_mod.take_last() is None
        assert profile_mod.recent() == []
        assert not profile_mod._active  # no leaked registrations
        assert tracing.current_span() is None
    finally:
        h.close()


def test_profile_registry_drains_after_profiled_query(tmp_path):
    """_active must be empty after the profiled query finishes (errors
    included), or current() stops being an empty-dict check."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        _seed_groupby(h, index="dr", n=50)
        h.client.query("dr", "Count(Row(a=1))", profile=True)
        assert not profile_mod._active
        from pilosa_tpu.server.client import ClientError

        with pytest.raises(ClientError):
            h.client.query("dr", "Bogus(Row(a=1))", profile=True)
        assert not profile_mod._active
    finally:
        h.close()


# ------------------------------------------------- slow-query log + ring


def test_slow_query_logged_with_profile_and_ring(tmp_path):
    """A query slower than long-query-time logs its full profile JSON and
    lands in GET /debug/queries marked slow."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        log = CaptureLogger()
        h.api.long_query_time = 0.0  # everything is slow
        h.api.logger = log
        profile_mod.clear_recent()
        h.client.create_index("sq")
        h.client.create_field("sq", "f")
        h.client.query("sq", "Set(1, f=10)")
        h.client.query("sq", "Count(Row(f=10))")

        slow = [line for line in log.lines if "SLOW QUERY" in line]
        assert len(slow) == 2
        assert all("profile=" in line for line in slow)
        # the embedded JSON parses back to the span tree
        tree = json.loads(slow[-1].split("profile=", 1)[1])
        assert tree["spans"]["name"] == "query"
        assert tree["slow"] is True
        assert "Count" in tree["query"]

        recent = h.client._request("GET", "/debug/queries")
        assert [p["index"] for p in recent] == ["sq", "sq"]
        assert all(p["slow"] for p in recent)
        # newest first: the Count came after the Set
        assert recent[0]["query"].startswith("Count")
    finally:
        h.close()


def test_debug_queries_ring_is_bounded(tmp_path):
    profile_mod.clear_recent()
    for i in range(profile_mod.MAX_RECENT + 10):
        profile_mod.begin("ring", f"Count(Row(f={i}))").finish()
    recent = profile_mod.recent()
    assert len(recent) == profile_mod.MAX_RECENT
    # oldest entries fell off; newest is first
    assert recent[0]["query"] == \
        f"Count(Row(f={profile_mod.MAX_RECENT + 9}))"
    profile_mod.clear_recent()


def test_debug_traces_requires_memory_tracer(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        off = h.client._request("GET", "/debug/traces")
        assert off["enabled"] is False and off["spans"] == []

        t = tracing.InMemoryTracer(max_spans=50)
        tracing.set_tracer(t)
        try:
            h.client.create_index("tr")
            h.client.create_field("tr", "f")
            h.client.query("tr", "Count(Row(f=1))")
            on = h.client._request("GET", "/debug/traces")
            assert on["enabled"] is True and on["maxSpans"] == 50
            names = {s["name"] for s in on["spans"]}
            assert "api.Query" in names
            assert any(n.startswith("http.POST") for n in names)
            # ring retention: never more than maxSpans live spans
            for _ in range(30):
                h.client.query("tr", "Count(Row(f=1))")
            on = h.client._request("GET", "/debug/traces")
            assert len(on["spans"]) <= 50
        finally:
            tracing.set_tracer(None)
    finally:
        h.close()


# -------------------------------------------------- exposition formats


def test_prometheus_escaping_and_histogram_validity():
    """Label values with quotes/backslashes/newlines must not corrupt the
    line-based exposition, and timing series must be valid cumulative
    histograms."""
    s = StatsClient()
    s.count("esc", 1, tags={"q": 'he said "hi"', "b": "a\\b", "n": "x\ny"})
    s.count("esc", 2, tags={"q": "plain"})
    values = (0.0002, 0.003, 0.003, 0.07, 1.5)
    for v in values:
        s.timing("lat_seconds", v, tags={"op": "x"})

    text = s.prometheus_text()
    assert '\\"hi\\"' in text
    assert "a\\\\b" in text
    assert "x\\ny" in text

    samples, families = _parse_prometheus(text)
    assert len(families) == len(set(families)), "duplicate # TYPE lines"
    assert "pilosa_tpu_esc_total" in families
    assert "pilosa_tpu_lat_seconds" in families

    buckets, count, total = _histogram_series(
        samples, "pilosa_tpu_lat_seconds", ['op="x"'])
    assert count == len(values)
    assert total == pytest.approx(sum(values))
    # one cumulative sample per configured bound plus +Inf
    assert len(buckets) == len(TIMING_BUCKETS) + 1
    cum = [c for _, c in buckets]
    assert cum == sorted(cum), "bucket counts must be cumulative"
    assert buckets[-1][0] == float("inf") and buckets[-1][1] == count
    # spot-check placement: two 3ms samples land at the 5ms bound
    by_bound = dict(buckets)
    assert by_bound[0.005] - by_bound[0.001] == 2


def test_expvar_quantiles_move_with_the_data():
    s = StatsClient()
    for _ in range(50):
        s.timing("q", 0.002)
    for _ in range(50):
        s.timing("q", 9.0)
    t = json.loads(s.expvar_json())["timings"]["q"]
    assert t["count"] == 100
    assert 0.001 <= t["p50"] <= 0.0025  # half the mass in the 2.5ms bucket
    assert t["p99"] > 1.0  # the slow half drags the tail up


def test_concurrent_stats_hammer():
    """Counters/timings/gauges hammered from many threads while both
    exposition formats are polled: every poll parses, counters are
    monotonic, and the final totals are exact."""
    s = StatsClient()
    n_threads, n_iter = 8, 300
    start = threading.Barrier(n_threads + 1)

    def work(i):
        start.wait()
        for j in range(n_iter):
            s.count("ham_c", 1, tags={"w": str(i % 2)})
            s.timing("ham_t", 0.001 * (j % 7))
            s.gauge("ham_g", j)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    start.wait()

    def total_of(data):
        return sum(v for k, v in data["counters"].items()
                   if k.startswith("ham_c"))

    last = 0
    for _ in range(25):
        samples, families = _parse_prometheus(s.prometheus_text())
        assert len(families) == len(set(families))
        data = json.loads(s.expvar_json())
        total = total_of(data)
        assert total >= last, "counter went backwards under concurrency"
        last = total

    for t in threads:
        t.join()
    data = json.loads(s.expvar_json())
    assert total_of(data) == n_threads * n_iter
    assert data["timings"]["ham_t"]["count"] == n_threads * n_iter
    samples, _ = _parse_prometheus(s.prometheus_text())
    assert samples[("pilosa_tpu_ham_t_bucket", 'le="+Inf"')] == \
        n_threads * n_iter


def test_per_route_request_metrics(tmp_path):
    """Requests are tagged with the matched route PATTERN (bounded
    cardinality) + method + status; errors are counted, unknown paths as
    route="unmatched"."""
    from pilosa_tpu.core import Holder
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.client import Client, ClientError
    from pilosa_tpu.server.http_server import PilosaHTTPServer

    holder = Holder(str(tmp_path)).open()
    reg = StatsClient()
    srv = PilosaHTTPServer(API(holder), host="127.0.0.1", port=0,
                           stats=reg).start()
    try:
        c = Client(srv.address)
        c.create_index("i")
        c.create_field("i", "f")
        c.query("i", "Count(Row(f=1))")
        with pytest.raises(ClientError):
            c._request("GET", "/definitely/not/a/route")

        # metrics are recorded AFTER the response bytes go out (so failed
        # writes are counted too) — poll briefly for the handler thread
        qlabels = ('method="POST",route="/index/(?P<index>[^/]+)/query",'
                   'status="200"')
        deadline = time.time() + 2.0
        while True:
            samples, _ = _parse_prometheus(reg.prometheus_text())
            try:
                assert samples[("pilosa_tpu_http_request_seconds_count",
                                qlabels)] == 1
                assert samples[
                    ("pilosa_tpu_http_errors_total",
                     'method="GET",route="unmatched",status="404"')] == 1
                break
            except (KeyError, AssertionError):
                if time.time() > deadline:
                    raise
                time.sleep(0.01)
        # successes are NOT counted as errors
        assert ("pilosa_tpu_http_errors_total", qlabels) not in samples
    finally:
        srv.stop()
        holder.close()


# ------------------------------------------------------ runtime monitor


def test_runtime_monitor_clean_shutdown_and_device_gauges():
    reg = StatsClient()
    mon = RuntimeMonitor(reg, interval=1.0)
    before = {t.ident for t in threading.enumerate()}
    mon.start()
    assert mon._thread.is_alive()
    mon.stop()
    assert not mon._thread.is_alive()
    leaked = {t.ident for t in threading.enumerate()} - before
    assert not leaked, "monitor left a thread behind"

    # device sampling with a live jax backend must not crash; on backends
    # without memory introspection (CPU) it simply emits nothing
    import jax

    jax.devices()  # ensure the backend is initialized
    mon.sample()
    _, gauges, _ = reg.snapshot()
    names = {name for name, _ in gauges}
    assert "uptime_seconds" in names and "threads" in names
    for name, labels in gauges:
        if name.startswith("device_"):
            assert dict(labels)["device"]  # tagged per device


# ------------------------------------------------------ cluster fan-out


def test_cluster_fanout_node_spans_and_profile():
    """A fan-out query produces one cluster.mapReduce.node span per
    target node on the coordinator's trace, and a coordinator profile
    captures them (per-node timings merged at the coordinator)."""
    from tests.harness import ClusterHarness

    t = tracing.InMemoryTracer()
    tracing.set_tracer(t)
    c = ClusterHarness(2)
    try:
        c[0].client.create_index("cf")
        c[0].client.create_field("cf", "f")
        c[0].client.import_bits("cf", "f", [3, 3], [1, SHARD_WIDTH + 1])
        non_owner = c.non_owner_of("cf", 0)
        t.clear()
        resp = non_owner.client.query("cf", "Count(Row(f=3))",
                                      profile=True)
        assert resp["results"] == [2]

        node_spans = t.find("cluster.mapReduce.node")
        assert node_spans
        assert len({s.trace_id for s in node_spans}) == 1
        assert any(s.tags.get("remote") for s in node_spans), \
            "no remote fan-out span"
        assert all(s.duration is not None for s in node_spans)

        prof = resp["profile"]
        assert prof is not None
        prof_nodes = [s for s in _walk(prof["spans"])
                      if s["name"] == "cluster.mapReduce.node"]
        assert len(prof_nodes) == len(node_spans)
        assert prof["duration"] >= max(
            s["duration"] for s in prof_nodes)
    finally:
        tracing.set_tracer(None)
        c.close()


# ------------------------------------------- stage spans and their counters


STAGES = {  # span -> parent, for a stacked Count (ISSUE 26 section 2)
    "api.Query": "query",
    "pql.parse": "api.Query",
    "exec.translate": "api.Query",
    "executor.Execute": "api.Query",
    "executor.executeCount": "executor.Execute",
    "exec.plan": "executor.executeCount",
    "stack.lookup": "executor.executeCount",
    "dispatch.queue": "executor.executeCount",
    "dispatch.lock_wait": "executor.executeCount",
    "stacked.kernel": "executor.executeCount",
    "dispatch.account": "executor.executeCount",
    "dispatch.fetch": "executor.executeCount",
}


def _seed_count(h, index="sc", n_shards=4):
    h.api.create_index(index)
    for field in ("f", "g"):
        h.api.create_field(index, field)
        h.api.import_bits(
            index, field, [1] * n_shards,
            [s * SHARD_WIDTH + 5 for s in range(n_shards)])


def _parents(node, parent=None, out=None):
    out = {} if out is None else out
    out.setdefault(node["name"], set()).add(parent)
    for child in node["children"]:
        _parents(child, node["name"], out)
    return out


def test_profiled_count_yields_every_stage_span(tmp_path, monkeypatch):
    """A profiled Count through API.query: every stage span, each under
    the span that was current where the work happened; children's
    durations sum to no more than the parent's; self >= 0; cpu within the
    wall (every profile clocked here, not one in CPU_SAMPLE)."""
    from pilosa_tpu.exec.executor import ExecOptions

    monkeypatch.setattr(profile_mod, "CPU_SAMPLE", 1)
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        _seed_count(h)
        pql = "Count(Intersect(Row(f=1), Row(g=1)))"
        assert h.api.query("sc", pql) == [4]
        profile_mod.take_last()
        assert h.api.query("sc", pql,
                           options=ExecOptions(profile=True)) == [4]
        root = profile_mod.take_last()["spans"]
    finally:
        h.close()
    assert root["name"] == "query"
    parents = _parents(root)
    for name, parent in STAGES.items():
        assert parents.get(name) == {parent}, (name, parents.get(name))
    lookups = [s for s in _walk(root) if s["name"] == "stack.lookup"]
    assert [s["tags"]["outcome"] for s in lookups] == ["hit", "hit"]
    queue, = [s for s in _walk(root) if s["name"] == "dispatch.queue"]
    assert queue["tags"] == {"role": "leader", "batch": 1}
    kernel, = [s for s in _walk(root) if s["name"] == "stacked.kernel"]
    assert "lock_wait_seconds" not in kernel["tags"]
    for node in _walk(root):
        assert node["self"] >= 0 and node["selfCpu"] >= 0
        assert 0 <= node["cpu"] <= node["duration"] + 1e-3
        # (the tree's numbers are rounded to a tenth of a microsecond)
        assert sum(c["duration"] for c in node["children"]) \
            <= node["duration"] + 1e-6
        assert node["self"] == pytest.approx(
            node["duration"] - sum(c["duration"] for c in node["children"]),
            abs=1e-6)


def test_one_profile_in_cpu_sample_is_clocked_and_weighed(monkeypatch):
    """The thread-CPU clock is read for one profile in CPU_SAMPLE; its
    spans count that many times in the CPU sums, the others not at all,
    so the sums stay an unbiased estimate and every span is counted."""
    import itertools

    monkeypatch.setattr(profile_mod, "CPU_SAMPLE", 4)
    monkeypatch.setattr(profile_mod, "_turn", itertools.count())
    zero = dict.fromkeys(("count", "cpu_seconds", "self_cpu_seconds"), 0)
    before = tracing.span_stats().get("sampled", zero)
    spans = []
    for _ in range(8):
        prof = profile_mod.begin("i", "q")
        with tracing.with_span(prof.root):
            with tracing.start_span("sampled") as span:
                sum(range(20000))
        tree = prof.finish()["spans"]
        spans.append(span)
        assert tree["children"][0]["cpu"] == (
            None if span.cpu is None else round(span.cpu, 7))
    clocked = [s for s in spans if s.cpu is not None]
    assert [s.cpu is not None for s in spans] == [True, False, False, False] * 2
    assert all(s.cpu_weight == 4 and s.self_cpu == s.cpu for s in clocked)
    assert all(s.self_cpu is None and s.duration > 0 for s in spans
               if s.cpu is None)
    after = tracing.span_stats()["sampled"]
    assert after["count"] - before["count"] == 8
    # less the clock's own read inside each clocked leaf
    read = tracing.clock_read_cost()
    assert after["cpu_seconds"] - before["cpu_seconds"] == pytest.approx(
        4 * sum(s.cpu - read for s in clocked))


def test_debug_vars_spans_process_and_holder(tmp_path):
    """/debug/vars: `spans` gains one tree a profiled query and nothing
    for an unprofiled one; `process.cpu_seconds` never falls;
    `holder.cache_flushes` counts flush_caches runs."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        _seed_count(h)
        pql = "Count(Intersect(Row(f=1), Row(g=1)))"
        h.client.query("sc", pql)

        def read():
            return h.client._request("GET", "/debug/vars")

        v0 = read()
        for _ in range(3):
            h.client.query("sc", pql)
        v1 = read()
        assert v1["spans"] == v0["spans"]  # nothing live: nothing counted
        n = 5
        for _ in range(n):
            h.client.query("sc", pql, profile=True)
        v2 = read()
        for name in list(STAGES) + ["query"]:
            row = v2["spans"][name]
            assert set(row) == {"count", "seconds", "self_seconds",
                                "cpu_seconds", "self_cpu_seconds"}
            per_query = 2 if name in ("stack.lookup", "exec.translate") else 1
            assert row["count"] - v1["spans"].get(
                name, {"count": 0})["count"] == n * per_query, name
        cpu = [v["process"]["cpu_seconds"] for v in (v0, v1, v2)]
        assert cpu == sorted(cpu) and cpu[0] > 0
        assert set(v2["holder"]) == {
            "cache_flushes", "cache_flush_seconds",
            "shard_list_rebuilds", "shard_list_seconds",
            "row_set_walks", "row_set_hits", "row_set_seconds"}
        assert v2["holder"]["cache_flushes"] == 0
        assert v2["holder"]["cache_flush_seconds"] == 0.0
        h.holder.flush_caches()
        v3 = read()
        assert v3["holder"]["cache_flushes"] == 1
        assert v3["holder"]["cache_flush_seconds"] >= 0
    finally:
        h.close()


def test_a_flush_held_open_is_counted_at_read(tmp_path):
    """cache_flush_seconds includes what a running flush has taken so far,
    so after - before is the flushing inside a window wherever it falls."""
    from pilosa_tpu.core import Holder

    holder = Holder(str(tmp_path), cache_flush_interval=0).open()
    try:
        idx = holder.create_index("fl")
        idx.create_field("f").set_bit(1, 3)
        frag = next(iter(holder._all_fragments()))
        entered, release = threading.Event(), threading.Event()

        def held_flush():
            entered.set()
            release.wait(30)

        frag.flush_cache = held_flush
        t = threading.Thread(target=holder.flush_caches)
        t.start()
        assert entered.wait(30)
        first = holder.flush_stats()
        time.sleep(0.01)
        second = holder.flush_stats()
        assert first["cache_flushes"] == second["cache_flushes"] == 0
        assert second["cache_flush_seconds"] > first["cache_flush_seconds"] > 0
        release.set()
        t.join(30)
        done = holder.flush_stats()
        assert done["cache_flushes"] == 1
        assert done["cache_flush_seconds"] >= second["cache_flush_seconds"]
        assert holder.flush_stats() == done  # nothing running: it stands
    finally:
        release.set()
        holder.close()


def test_stage_spans_reach_the_profilers_trace(tmp_path):
    """Under jax.profiler.start_trace a profiled query leaves host events
    named after its spans in the .xplane.pb, and the count program is
    called by its own name, not `fn`."""
    import glob

    import jax

    from pilosa_tpu.exec.executor import ExecOptions

    h = ServerHarness(data_dir=str(tmp_path / "data"))
    try:
        _seed_count(h)
        pql = "Count(Intersect(Row(f=1), Row(g=1)))"
        h.api.query("sc", pql)
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            h.api.query("sc", pql, options=ExecOptions(profile=True))
            h.holder.flush_caches()
        finally:
            jax.profiler.stop_trace()
    finally:
        h.close()
    path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes for line in plane.lines for e in line.events}
    for want in ("query", "executor.Execute", "dispatch.fetch",
                 "stack.lookup", "holder.flush_caches",
                 "PjitFunction(count_tree)"):
        assert want in names, want
    assert "PjitFunction(fn)" not in names


def test_every_debug_endpoint_is_in_the_operations_inventory():
    """docs/operations.md names, in backticks, every path of the /debug
    index — and the index holds no path whose route is gone."""
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "docs", "operations.md")) as f:
        named = set(re.findall(r"`(/debug[^`]*)`", f.read()))
    h = ServerHarness()
    try:
        index = h.client._request("GET", "/debug")["endpoints"]
        paths = {e["path"] for e in index}
        assert len(paths) > 20 and all(e["description"] for e in index)
        assert paths - named == set()
        assert "/debug/batching" not in paths
        with pytest.raises(Exception, match="404|not found"):
            h.client._request("GET", "/debug/batching")
    finally:
        h.close()

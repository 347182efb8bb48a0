"""Tracing subsystem (reference: tracing/tracing.go + handler/client
inject-extract). Covers span nesting, nop fast path, and cross-node HTTP
propagation through a live 2-node cluster query."""

import pytest

from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.logger import CaptureLogger


@pytest.fixture
def tracer():
    t = tracing.InMemoryTracer()
    tracing.set_tracer(t)
    yield t
    tracing.set_tracer(None)


def test_nop_by_default():
    tracing.set_tracer(None)
    with tracing.start_span("x") as span:
        assert span is None  # zero-allocation fast path
    assert tracing.current_span() is None


def test_span_nesting_and_finish(tracer):
    with tracing.start_span("parent", index="i") as p:
        with tracing.start_span("child") as c:
            assert c.trace_id == p.trace_id
            assert c.parent_id == p.span_id
            assert tracing.current_span() is c
        assert tracing.current_span() is p
    assert tracing.current_span() is None
    names = [s.name for s in tracer.spans]
    assert names == ["child", "parent"]  # children finish first
    assert all(s.duration is not None for s in tracer.spans)
    assert tracer.find("parent")[0].tags == {"index": "i"}


def test_inject_and_extract_headers(tracer):
    assert tracing.inject_headers() == {}
    with tracing.start_span("origin") as origin:
        headers = tracing.inject_headers()
        assert headers[tracing.TRACE_HEADER] == origin.trace_id
        assert headers[tracing.PARENT_HEADER] == origin.span_id
    with tracing.span_from_headers("remote", headers) as remote:
        assert remote.trace_id == origin.trace_id
        assert remote.parent_id == origin.span_id


def test_span_from_headers_without_context(tracer):
    with tracing.span_from_headers("h", {}) as span:
        assert span.parent_id is None


def test_extract_headers_case_insensitive(tracer):
    """HTTP/2 proxies and some test clients lowercase header names;
    extraction must not depend on the canonical casing."""
    with tracing.start_span("origin") as origin:
        headers = tracing.inject_headers()
    lowered = {k.lower(): v for k, v in headers.items()}
    assert lowered != headers  # the canonical names ARE mixed-case
    with tracing.span_from_headers("remote", lowered) as remote:
        assert remote.trace_id == origin.trace_id
        assert remote.parent_id == origin.span_id
    # mixed garbage casing also resolves
    weird = {"x-pILOSA-tRACE-iD": "t123", "X-PILOSA-SPAN-ID": "s456"}
    with tracing.span_from_headers("remote2", weird) as remote:
        assert remote.trace_id == "t123"
        assert remote.parent_id == "s456"


def test_trace_headers_reinjected_on_each_request(tracer):
    """Every Client._request call injects the CURRENT span's headers —
    so a replica retry (a second request inside the same span) carries
    the trace context again, not just the first attempt."""
    import http.server
    import threading

    from pilosa_tpu.server.client import Client

    seen = []

    class Sink(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            seen.append(dict(self.headers.items()))
            body = b"{}"
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        client = Client(f"http://127.0.0.1:{srv.server_address[1]}")
        with tracing.start_span("fanout") as span:
            client.status()  # first attempt
            client.status()  # the "retry": same span, new request
        assert len(seen) == 2
        for headers in seen:
            got = {k.lower(): v for k, v in headers.items()}
            assert got[tracing.TRACE_HEADER.lower()] == span.trace_id
            assert got[tracing.PARENT_HEADER.lower()] == span.span_id
    finally:
        srv.shutdown()


def test_executor_spans(tracer, tmp_path):
    from tests.harness import ServerHarness

    h = ServerHarness(data_dir=str(tmp_path))
    try:
        h.client.create_index("ti")
        h.client.create_field("ti", "f")
        h.client.query("ti", "Set(1, f=10)")
        h.client.query("ti", "Count(Row(f=10))")
    finally:
        h.close()
    assert tracer.find("api.Query")
    assert tracer.find("executor.Execute")
    assert tracer.find("executor.executeCount")
    # HTTP server spans carry the query trace id
    http_spans = [s for s in tracer.spans if s.name.startswith("http.POST")]
    assert http_spans
    exec_span = tracer.find("executor.Execute")[-1]
    assert any(s.trace_id == exec_span.trace_id for s in http_spans)


def test_cross_node_trace_propagation(tracer):
    """A fan-out query must carry one trace id through the remote node's
    HTTP layer (reference: handler extractTracing / client inject)."""
    from tests.harness import ClusterHarness

    c = ClusterHarness(2)
    try:
        c[0].client.create_index("ti")
        c[0].client.create_field("ti", "f")
        # bits across two shards so the query fans out to both nodes
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        c[0].client.import_bits(
            "ti", "f", [10, 10], [5, SHARD_WIDTH + 5])
        # query via a node that does NOT own shard 0 -> remote fan-out
        non_owner = c.non_owner_of("ti", 0)
        tracer.clear()
        assert non_owner.client.query(
            "ti", "Count(Row(f=10))")["results"] == [2]
    finally:
        c.close()
    remote_spans = [s for s in tracer.spans
                    if s.name.startswith("http.POST") and s.parent_id]
    assert remote_spans, "no remote http span continued a trace"
    exec_spans = tracer.find("executor.Execute")
    trace_ids = {s.trace_id for s in exec_spans}
    assert any(s.trace_id in trace_ids for s in remote_spans)


def test_slow_query_log(tmp_path):
    from pilosa_tpu.core import Holder
    from pilosa_tpu.server.api import API

    log = CaptureLogger()
    holder = Holder(str(tmp_path))
    holder.open()
    try:
        api = API(holder, long_query_time=0.0, logger=log)
        api.create_index("i")
        api.create_field("i", "f")
        api.query("i", "Count(Row(f=3))")
    finally:
        holder.close()
    assert any("SLOW QUERY" in line and "Count" in line for line in log.lines)


# -- a span's own time and CPU, and the nothing-live path --------------------


def test_nothing_live_is_one_shared_noop():
    """With the nop tracer and no active span every start_span (and a
    span_from_headers without headers) hands back the same object: no
    Span is allocated and the per-name sums do not move."""
    tracing.set_tracer(None)
    before = tracing.span_stats()
    made = []
    real_init = tracing.Span.__init__

    def counting_init(self, *a, **k):
        made.append(a[0])
        real_init(self, *a, **k)

    tracing.Span.__init__ = counting_init
    try:
        first = tracing.start_span("x", index="i")
        assert tracing.start_span("y") is first
        assert tracing.span_from_headers("h", {}, method="GET") is first
        with first as span:
            assert span is None
        tracing.end_current("exec.plan")  # nothing to end: a no-op
    finally:
        tracing.Span.__init__ = real_init
    assert made == []
    assert tracing.span_stats() == before


def test_span_self_time_and_cpu(tracer):
    """Children report into the parent as they finish: their durations sum
    to no more than the parent's, self >= 0, 0 <= cpu <= duration + 1 ms."""
    with tracing.start_span("parent"):
        with tracing.start_span("a"):
            sum(range(20000))
        with tracing.start_span("b"):
            with tracing.start_span("c"):
                sum(range(20000))
    by = {s.name: s for s in tracer.spans}
    assert set(by) == {"parent", "a", "b", "c"}
    for s in by.values():
        assert s.self_time >= 0 and s.self_cpu >= 0
        assert 0 <= s.cpu <= s.duration + 1e-3
        assert s.self_time <= s.duration and s.self_cpu <= s.cpu
    assert by["a"].duration + by["b"].duration <= by["parent"].duration
    assert by["c"].duration <= by["b"].duration
    assert by["parent"].self_time == pytest.approx(
        by["parent"].duration - by["a"].duration - by["b"].duration)
    assert by["a"].self_time == by["a"].duration  # a leaf is all its own
    d = by["b"].to_dict()
    assert d["self"] == by["b"].self_time and d["selfCpu"] == by["b"].self_cpu
    assert d["cpu"] == by["b"].cpu
    again = tracing.Span.from_dict(d)
    assert (again.cpu, again.self_time, again.self_cpu) == (
        d["cpu"], d["self"], d["selfCpu"])


def test_child_on_another_thread_is_not_taken_off_self(tracer):
    """A worker thread's span runs beside its parent: it keeps the parent
    id, but the parent's own time is not reduced by it."""
    import threading

    with tracing.start_span("parent") as parent:
        def work():
            with tracing.with_span(parent):
                with tracing.start_span("worker"):
                    sum(range(20000))

        t = threading.Thread(target=work)
        t.start()
        t.join()
    worker, = tracer.find("worker")
    assert worker.parent_id == parent.span_id
    assert parent.self_time == parent.duration


def test_end_current_ends_a_stage_early(tracer):
    """exec.plan runs to the first stack lookup, not to the end of its
    block: end_current finishes it there, the `with` exits as a no-op and
    the next stage is its sibling."""
    with tracing.start_span("call") as call:
        with tracing.start_span("exec.plan"):
            with tracing.start_span("exec.plan"):  # a nested call's plan
                tracing.end_current("stack.lookup")  # another name: no-op
                assert tracing.current_span().name == "exec.plan"
                tracing.end_current("exec.plan")
                assert tracing.current_span() is call
                with tracing.start_span("stack.lookup") as lookup:
                    assert lookup.parent_id == call.span_id
        assert tracing.current_span() is call
    assert len(tracer.find("exec.plan")) == 2  # each published once
    assert [s.name for s in tracer.spans].count("stack.lookup") == 1


def test_span_stats_sum_by_name(tracer):
    before = tracing.span_stats().get("counted", {"count": 0})["count"]
    for _ in range(3):
        with tracing.start_span("counted"):
            pass
    row = tracing.span_stats()["counted"]
    assert row["count"] == before + 3
    assert set(row) == {"count", "seconds", "self_seconds", "cpu_seconds",
                        "self_cpu_seconds"}
    assert row["seconds"] >= row["self_seconds"] >= 0
    assert row["cpu_seconds"] >= row["self_cpu_seconds"] >= 0

"""Overload-safe serving (ISSUE 15): cost-aware admission control,
deadline propagation, and the degradation ladder.

The acceptance contract pinned here:

- malformed `X-Request-Deadline` -> 400 at the HTTP edge;
- expired-on-arrival -> 504 with ZERO dispatches (stacked counters
  flat), and a deadline that lapses in the admission queue is dropped
  before ever touching the dispatch lock;
- the deadline survives coordinator fan-out to a 2-node cluster;
- `--admission off` (the default) constructs nothing and leaves the
  legacy path untouched;
- every shedding site (ingest, resize-queue, admission)
  rejects through the one jittered `shed_reject` helper with the
  shared `rejections_total{site,class}` counter and the
  `X-Pilosa-Shed` marker;
- a shedding peer is retried on the SAME replica once
  (cluster.node_overload), not logged as a dead one
  (cluster.node_unready).
"""

import json
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.pql import parse
from pilosa_tpu.server import admission
from pilosa_tpu.server.api import (GatewayTimeoutError,
                                   ServiceUnavailableError, shed_reject)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import devhealth, flightrec, workload
from pilosa_tpu.utils.stats import global_stats
from tests.harness import ClusterHarness, ServerHarness


@pytest.fixture(autouse=True)
def _pristine():
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)
    workload.reset()
    yield
    devhealth.stop()
    workload.reset()
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)


def _counter(name):
    counters, _, _ = global_stats.snapshot()
    return sum(v for k, v in counters.items()
               if (k[0] if isinstance(k, tuple) else k) == name)


def _dispatches(api):
    local = getattr(api.executor, "local", api.executor)
    return local._stacked.counters()[0]


def _post(url, body=b"", headers=None):
    """(status, headers, json_body) — 4xx/5xx returned, not raised."""
    req = urllib.request.Request(url, data=body, method="POST")
    req.add_header("Content-Type", "text/plain")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read().decode())


# ------------------------------------------------------------ unit: classes


def test_classify_defaults():
    assert admission.classify(query=parse("Count(Row(f=1))")) \
        == admission.INTERACTIVE
    assert admission.classify(query=parse("Set(1, f=1)")) \
        == admission.BATCH
    assert admission.classify(path_internal=True) == admission.INTERNAL
    # the validated header always wins
    assert admission.classify(header="batch",
                              query=parse("Count(Row(f=1))")) \
        == admission.BATCH


def test_parse_deadline_forms():
    assert admission.parse_deadline("2.5") == pytest.approx(2.5)
    assert admission.parse_deadline("250ms") == pytest.approx(0.25)
    assert admission.parse_deadline("1m30s") == pytest.approx(90.0)
    # absolute epoch deadline, relative to a pinned "now"
    assert admission.parse_deadline("@1000.5", now=1000.0) \
        == pytest.approx(0.5)
    assert admission.parse_deadline("@999", now=1000.0) < 0  # expired
    for bad in ("", "soon", "12parsecs", "@then"):
        with pytest.raises(ValueError):
            admission.parse_deadline(bad)


def test_token_bucket_math():
    b = admission.TokenBucket(100.0, burst_seconds=2.0)  # 100 ms/s
    assert b.burst == pytest.approx(200.0)
    assert b.tokens == pytest.approx(200.0)  # starts full
    now = time.monotonic()
    assert b.try_debit(150.0, now)
    assert b.tokens == pytest.approx(50.0)
    assert not b.try_debit(100.0, now)  # dry
    # refill accrues rate * dt, capped at burst
    b.refill(now + 0.5)
    assert b.tokens == pytest.approx(100.0)
    b.refill(now + 100.0)
    assert b.tokens == pytest.approx(200.0)
    # deficit: time until cost fits at the refill rate
    b.tokens = 0.0
    assert b.deficit_seconds(50.0) == pytest.approx(0.5)


def _controller(**kw):
    kw.setdefault("capacity_ms_per_s", 1000.0)
    return admission.AdmissionController(**kw)


def test_admit_and_queue_full_rejection():
    adm = _controller(capacity_ms_per_s=0.001, queue_depth=0)
    try:
        # a full bucket always grants one burst-capped request; drain it
        adm.admit(admission.INTERACTIVE, 1.0)
        # now the bucket is dry (refill is ~0.0006 ms/s) and
        # queue_depth 0 -> immediate 503-shaped rejection
        with pytest.raises(admission.Rejected) as ei:
            adm.admit(admission.INTERACTIVE, 1.0)
        assert ei.value.retry_after > 0
        assert ei.value.qclass == admission.INTERACTIVE
        snap = adm.snapshot()
        assert snap["classes"]["interactive"]["rejected"] == 1
    finally:
        adm.close()


def test_admit_expired_in_queue_never_dispatches():
    adm = _controller(capacity_ms_per_s=0.001, queue_depth=8,
                      queue_timeout=30.0)
    try:
        adm.admit(admission.INTERACTIVE, 1.0)  # drain the full bucket
        t0 = time.monotonic()
        with pytest.raises(admission.Expired):
            adm.admit(admission.INTERACTIVE, 5.0,
                      deadline=time.monotonic() + 0.15)
        assert time.monotonic() - t0 < 5.0  # gave up at the deadline
        assert adm.snapshot()["classes"]["interactive"][
            "expired_dropped"] == 1
    finally:
        adm.close()


def test_ladder_escalates_immediately_deescalates_one_rung_with_hold():
    adm = _controller()
    try:
        signals = [(admission.LIFEBOAT, "forced")]
        adm._target_state = lambda: signals[0]
        now = time.monotonic()
        assert adm.maybe_update_ladder(now + 2) == admission.LIFEBOAT
        # recovery: target NORMAL, but the ladder holds the rung, then
        # steps DOWN one rung at a time
        signals[0] = (admission.NORMAL, "recovered")
        assert adm.maybe_update_ladder(now + 4) == admission.LIFEBOAT
        t_hold = now + 4 + admission.LADDER_HOLD_SECONDS
        assert adm.maybe_update_ladder(t_hold + 1) == admission.STALE_OK
        assert adm.maybe_update_ladder(
            t_hold + admission.LADDER_HOLD_SECONDS + 2) \
            == admission.SHED_BATCH
        kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
        assert kinds.count("admission.state") == 3  # edge-triggered
        assert adm.snapshot()["transitions"][-1]["to"] \
            == admission.SHED_BATCH
    finally:
        adm.close()


def test_lifeboat_rejects_batch_and_writes():
    adm = _controller()
    try:
        adm._target_state = lambda: (admission.LIFEBOAT, "forced")
        adm.maybe_update_ladder(time.monotonic() + 2)
        with pytest.raises(admission.Rejected):
            adm.admit(admission.BATCH, 1.0)
        with pytest.raises(admission.Rejected):
            adm.admit(admission.INTERACTIVE, 1.0, is_write=True)
        # interactive reads and internal traffic still flow
        assert adm.admit(admission.INTERACTIVE, 1.0) is not None
        assert adm.admit(admission.INTERNAL, 1.0) is not None
        assert adm.snapshot()["shed_by_state"][admission.LIFEBOAT] == 2
    finally:
        adm.close()


def test_shed_batch_parks_batch_even_with_tokens():
    adm = _controller(queue_timeout=0.2)
    try:
        adm._target_state = lambda: (admission.SHED_BATCH, "forced")
        adm.maybe_update_ladder(time.monotonic() + 2)
        assert adm.buckets[admission.BATCH].tokens > 1.0  # tokens banked
        t0 = time.monotonic()
        with pytest.raises(admission.Rejected):  # queued-only: times out
            adm.admit(admission.BATCH, 1.0)
        assert time.monotonic() - t0 >= 0.15
        # interactive is untouched at this rung
        assert adm.admit(admission.INTERACTIVE, 1.0) is not None
        assert adm.shed_merges()
        assert not adm.serving_stale()
    finally:
        adm.close()


def test_calibration_ewma_and_refund():
    adm = _controller()
    try:
        ticket = adm.admit(admission.INTERACTIVE, 100.0)
        tokens_after_debit = adm.buckets[admission.INTERACTIVE].tokens
        # measured 10ms against priced 100ms: refund ~90ms, EWMA dips
        adm.note_done(ticket, 0.010)
        assert adm._calibration < 1.0
        assert adm.buckets[admission.INTERACTIVE].tokens \
            > tokens_after_debit + 80.0
        # over-run drags the EWMA the other way
        t2 = adm.admit(admission.INTERACTIVE, 1.0)
        adm.note_done(t2, 1.0)
        assert adm._calibration > 0.9
    finally:
        adm.close()


def test_shed_reject_unifies_retry_after_and_counter():
    before = _counter("rejections_total")
    with pytest.raises(ServiceUnavailableError) as ei:
        shed_reject("testsite", "too busy", 4.0, qclass="batch")
    ra = float(ei.value.headers["Retry-After"])
    assert 4.0 <= ra <= 5.0  # jitter x1.0-1.25
    assert ei.value.headers["X-Pilosa-Shed"] == "testsite"
    assert _counter("rejections_total") == before + 1


# ------------------------------------------------------------ http surface


@pytest.fixture
def h(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path))
    yield h
    h.close()


def _seed(h, idx="adm"):
    h.client.create_index(idx)
    h.client.create_field(idx, "f")
    h.client.query(idx, "Set(3, f=1)")
    h.client.query(idx, f"Set({SHARD_WIDTH + 5}, f=1)")
    h.client.query(idx, "Count(Row(f=1))")  # warm the stacked path
    return idx


def test_malformed_deadline_is_400(h):
    idx = _seed(h)
    status, _, body = _post(f"{h.address}/index/{idx}/query",
                            b"Count(Row(f=1))",
                            {"X-Request-Deadline": "whenever"})
    assert status == 400
    assert "X-Request-Deadline" in body["error"]


def test_bad_query_class_is_400(h):
    idx = _seed(h)
    status, _, body = _post(f"{h.address}/index/{idx}/query",
                            b"Count(Row(f=1))",
                            {"X-Query-Class": "vip"})
    assert status == 400
    assert "X-Query-Class" in body["error"]


def test_expired_on_arrival_504_zero_dispatches(h):
    idx = _seed(h)
    before = _dispatches(h.api)
    status, _, body = _post(f"{h.address}/index/{idx}/query",
                            b"Count(Row(f=1))",
                            {"X-Request-Deadline": "-1"})
    assert status == 504
    assert "deadline" in body["error"]
    assert _dispatches(h.api) == before, \
        "expired work must never reach the dispatch lock"


def test_generous_deadline_serves_normally(h):
    idx = _seed(h)
    status, _, body = _post(f"{h.address}/index/{idx}/query",
                            b"Count(Row(f=1))",
                            {"X-Request-Deadline": "30s",
                             "X-Query-Class": "interactive"})
    assert status == 200
    assert body["results"] == [2]
    assert "stale" not in body


def test_admission_off_is_inert(h):
    idx = _seed(h)
    assert h.api._admission is None
    assert h.api.admission_stats() == {"enabled": False}
    assert not h.api.serving_stale()
    status, _, body = _post(f"{h.address}/index/{idx}/query",
                            b"Count(Row(f=1))")
    assert status == 200 and body["results"] == [2]


@pytest.fixture
def h_on(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path), admission="on")
    yield h
    h.close()


def test_admission_on_serves_and_reports(h_on):
    idx = _seed(h_on)
    status, _, body = _post(f"{h_on.address}/index/{idx}/query",
                            b"Count(Row(f=1))")
    assert status == 200 and body["results"] == [2]
    snap = h_on.client.debug_admission()
    assert snap["enabled"] and snap["state"] == "NORMAL"
    assert snap["classes"]["interactive"]["admitted"] >= 1
    assert snap["classes"]["batch"]["admitted"] >= 2  # the Sets
    # calibration learned from completed queries
    assert snap["calibration_samples"] >= 1


def test_admission_shed_503_with_retry_after(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path), admission="on",
                      admission_capacity=0.001,
                      admission_queue_depth=0)
    try:
        idx = _seed_off_path(h)
        # first request drains the (burst-capped) full bucket
        h.api._admission.admit(admission.INTERACTIVE, 1.0)
        before = _counter("rejections_total")
        status, headers, body = _post(f"{h.address}/index/{idx}/query",
                                      b"Count(Row(f=1))")
        assert status == 503
        assert float(headers["Retry-After"]) >= 1.0
        assert headers["X-Pilosa-Shed"] == "admission"
        assert _counter("rejections_total") == before + 1
        assert h.api.admission_stats()["classes"]["interactive"][
            "rejected"] >= 1
    finally:
        h.close()


def _seed_off_path(h, idx="adm"):
    """Seed data through the API directly (bypassing admission), for
    tests whose controller is configured to shed everything."""
    h.api.create_index(idx)
    h.api.create_field(idx, "f")
    h.api._query_admitted(idx, "Set(3, f=1)", None, None)
    return idx


def test_queue_lapsed_deadline_504_zero_dispatches(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path), admission="on",
                      admission_capacity=0.001,
                      admission_queue_depth=16,
                      admission_queue_timeout=30.0)
    try:
        idx = _seed_off_path(h)
        # drain the full bucket so the deadline-bearing request waits
        h.api._admission.admit(admission.INTERACTIVE, 1.0)
        before = _dispatches(h.api)
        t0 = time.monotonic()
        status, _, body = _post(f"{h.address}/index/{idx}/query",
                                b"Count(Row(f=1))",
                                {"X-Request-Deadline": "200ms"})
        assert status == 504
        assert time.monotonic() - t0 < 10.0  # dropped at the deadline,
        assert _dispatches(h.api) == before  # never dispatched
        assert h.api.admission_stats()["classes"]["interactive"][
            "expired_dropped"] == 1
    finally:
        h.close()


def test_debug_surfaces(h_on):
    _seed(h_on)
    # /debug index lists the endpoint
    paths = {e["path"] for e in
             h_on.client._request("GET", "/debug")["endpoints"]}
    assert "/debug/admission" in paths
    # /status?observability=true rolls the summary up
    status = h_on.client._request("GET", "/status?observability=true")
    local = status["observability"]["local"]
    assert local["admission"]["state"] == "NORMAL"
    assert local["admission"]["admitted"] >= 1


def test_stale_marker_on_stale_ok(h_on):
    idx = _seed(h_on)
    adm = h_on.api._admission
    adm._target_state = lambda: (admission.STALE_OK, "forced")
    adm.maybe_update_ladder(time.monotonic() + 2)
    status, _, body = _post(f"{h_on.address}/index/{idx}/query",
                            b"Count(Row(f=1))")
    assert status == 200
    assert body["stale"] is True


def test_ingest_sheds_interval_merges_not_overflow(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path), admission="on",
                      ingest_interval=0.05)
    try:
        idx = _seed(h, "ing")
        adm = h.api._admission
        adm._target_state = lambda: (admission.SHED_BATCH, "forced")
        adm.maybe_update_ladder(time.monotonic() + 2)
        assert h.api.ingest._shed_probe == adm.shed_merges
        h.client.import_bits(idx, "f", [2], [7])
        time.sleep(0.25)  # several ticks land while shedding
        snap = h.api.ingest.snapshot()
        assert snap["merges_shed"] >= 1
        assert snap["pending"]["entries"] >= 1  # deltas still buffered
    finally:
        h.close()


# ------------------------------------------------------------ cluster


def test_deadline_survives_cluster_fanout():
    from pilosa_tpu.cluster import ModHasher

    h = ClusterHarness(2, replica_n=1, hasher=ModHasher())
    try:
        h[0].client.create_index("cd")
        h[0].client.create_field("cd", "f")
        time.sleep(0.3)  # DDL broadcast settles
        n_shards = 6
        cols = [s * SHARD_WIDTH + 2 for s in range(n_shards)]
        h[0].client.import_bits("cd", "f", [1] * len(cols), cols)
        owners = {h[0].cluster.shard_nodes("cd", s)[0].id
                  for s in range(n_shards)}
        assert len(owners) == 2, "ModHasher should use both nodes"

        # a generous deadline rides the whole fan-out and serves
        resp = h[0].client.query("cd", "Count(Row(f=1))", deadline=30.0)
        assert resp["results"] == [n_shards]

        # expired-on-arrival at the coordinator: 504, and NO node
        # dispatched anything
        before = [_dispatches(n.api) for n in h.nodes]
        status, _, body = _post(
            f"{h[0].address}/index/cd/query", b"Count(Row(f=1))",
            {"X-Request-Deadline": "-0.5"})
        assert status == 504
        assert [_dispatches(n.api) for n in h.nodes] == before
    finally:
        h.close()


def test_peer_overload_retried_same_replica_not_marked_unready():
    from pilosa_tpu.cluster import ModHasher

    h = ClusterHarness(2, replica_n=1, hasher=ModHasher())
    try:
        h[0].client.create_index("ov")
        h[0].client.create_field("ov", "f")
        time.sleep(0.3)
        n_shards = 6
        cols = [s * SHARD_WIDTH + 2 for s in range(n_shards)]
        h[0].client.import_bits("ov", "f", [1] * len(cols), cols)

        # make the PEER shed (admission-style 503 with the X-Pilosa-Shed
        # marker) until the coordinator's CLIENT retry budget (2) is
        # exhausted — only then does the 503 reach the cluster executor,
        # whose same-replica overload retry then succeeds
        peer = h[1]
        real_query = peer.api.query
        state = {"shed": 3}

        def flaky_query(*a, **kw):
            if kw.get("options") is not None and kw["options"].remote \
                    and state["shed"] > 0:
                state["shed"] -= 1
                shed_reject("admission", "synthetic overload", 1,
                            qclass="interactive")
            return real_query(*a, **kw)

        peer.api.query = flaky_query
        resp = h[0].client.query("ov", "Count(Row(f=1))")
        assert resp["results"] == [n_shards]
        kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
        assert "cluster.node_overload" in kinds
        assert "cluster.node_unready" not in kinds
        assert state["shed"] == 0
    finally:
        h.close()


def test_peer_unready_503_still_flagged_unready():
    from pilosa_tpu.cluster import ModHasher

    h = ClusterHarness(2, replica_n=1, hasher=ModHasher())
    try:
        h[0].client.create_index("ur")
        h[0].client.create_field("ur", "f")
        time.sleep(0.3)
        n_shards = 6
        cols = [s * SHARD_WIDTH + 2 for s in range(n_shards)]
        h[0].client.import_bits("ur", "f", [1] * len(cols), cols)

        peer = h[1]
        real_query = peer.api.query

        def unready_query(*a, **kw):
            if kw.get("options") is not None and kw["options"].remote:
                raise ServiceUnavailableError("device link DOWN",
                                              retry_after=5)
            return real_query(*a, **kw)

        peer.api.query = unready_query
        # replica_n=1: the peer's shards have no replica, so the query
        # fails — but through the UNREADY path, not the overload one
        with pytest.raises(Exception):
            h[0].client.query("ur", "Count(Row(f=1))")
        kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
        assert "cluster.node_unready" in kinds
        assert "cluster.node_overload" not in kinds
    finally:
        h.close()


def test_zero_priced_dispatches():
    """price() must keep the planner's zero-dispatch contract — cost
    estimation can never be allowed to execute the query."""
    h = ServerHarness(admission="on")
    try:
        idx = _seed(h, "pz")
        adm = h.api._admission
        before = _dispatches(h.api)
        cost = adm.price(h.api.executor, h.api.holder.index(idx),
                         parse("GroupBy(Rows(f))"), None,
                         __import__("pilosa_tpu.exec",
                                    fromlist=["ExecOptions"])
                         .ExecOptions())
        assert cost >= admission.FALLBACK_COST_MS
        assert _dispatches(h.api) == before
    finally:
        h.close()

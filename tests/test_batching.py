"""Batched dispatch pipeline (ISSUE 9): query coalescer + vmapped
batched kernels + double-buffered launch/resolve.

The contract under test is BIT-IDENTITY: batched execution must return
exactly what the serial per-query path returns, for every padding
bucket, for mixed batchable/non-batchable traffic, and with per-query
error isolation (one bad member never sinks its batchmates). Plus the
serving-layer behaviors: coalescer fusing of concurrent arrivals,
window=0 leaving the legacy path untouched, overload 503 + Retry-After,
/debug/batching + the query-batch route, SLOW QUERY batch= attribution,
and the plan-layer `batched` annotation.
"""

import json
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec.stacked import BATCH_BUCKETS, batch_bucket
from pilosa_tpu.server.api import API, ApiError, ServiceUnavailableError
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.logger import CaptureLogger

from .harness import ServerHarness

N_SHARDS = 3
N_ROWS = 6


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One holder + two APIs over it: `legacy` (window=0, the reference
    behavior) and a plain executor. Module-scoped so the vmapped batch
    kernels compile once across the differential tests."""
    tmp = tmp_path_factory.mktemp("batching")
    holder = Holder(str(tmp)).open()
    api = API(holder)
    api.create_index("i")
    api.create_field("i", "f")
    api.create_field("i", "g")
    rng = np.random.default_rng(17)
    for fld in ("f", "g"):
        cols = rng.choice(N_SHARDS * SHARD_WIDTH, size=600, replace=False)
        rows = rng.integers(0, N_ROWS, size=600)
        api.import_bits("i", fld, rows.tolist(), cols.tolist())
    yield holder, api, Executor(holder)
    holder.close()


def _same_result(a, b):
    if hasattr(a, "segments") or hasattr(b, "segments"):
        return np.array_equal(a.columns(), b.columns())
    return a == b


# ------------------------------------------------------------ unit level


def test_batch_bucket_boundaries():
    assert BATCH_BUCKETS == (1, 4, 16, 64)
    assert batch_bucket(1) == 1
    assert batch_bucket(2) == 4
    assert batch_bucket(4) == 4
    assert batch_bucket(5) == 16
    assert batch_bucket(16) == 16
    assert batch_bucket(17) == 64
    assert batch_bucket(64) == 64
    # past the largest bucket the launcher chunks, never grows
    assert batch_bucket(100) == 64


# ------------------------------------------------- differential identity


def test_batched_bit_identical_across_buckets(env):
    """Randomized Row/Intersect/Union/Count corpus: execute_batch ==
    execute, member by member, with group sizes chosen to exercise
    every padding bucket (1, 4, 16, 64)."""
    holder, api, ex = env
    rng = np.random.default_rng(5)
    corpus = []
    # bucket 64: 17 same-signature members (batch_bucket(17) == 64)
    corpus += [f"Count(Row(f={rng.integers(0, N_ROWS)}))"
               for _ in range(17)]
    # bucket 16: 6 plane-family members of one signature
    corpus += [f"Row(g={rng.integers(0, N_ROWS)})" for _ in range(6)]
    # bucket 4: 3 combine members
    corpus += [f"Union(Row(f={rng.integers(0, N_ROWS)}), "
               f"Row(g={rng.integers(0, N_ROWS)}))" for _ in range(3)]
    # bucket 1: singletons reuse the ordinary (unbatched) kernels
    corpus += ["Count(Intersect(Row(f=1), Row(g=2)))",
               "Difference(Row(f=0), Row(g=0))"]
    # non-batchable + empty-row members ride along (the empty row
    # shares Count(Row)'s signature, so it joins the 17-member group)
    corpus += ["TopN(f, n=2)", "Count(Row(f=997))"]

    out = ex.execute_batch("i", list(corpus))
    assert len(out) == len(corpus)
    sizes = {}
    for pql, (res, err, bsize, fp) in zip(corpus, out):
        assert err is None, (pql, err)
        want = ex.execute("i", pql)
        assert _same_result(res[0], want[0]), pql
        assert fp, pql
        sizes[pql.split("(", 1)[0]] = max(
            sizes.get(pql.split("(", 1)[0], 0), bsize)
    # the 17+1-member Count(Row) group fused as ONE batch of 18
    # (occupancy, not the padded bucket, is what members report)
    assert sizes["Count"] == 18
    assert sizes["Row"] == 6
    assert sizes["Union"] == 3
    assert sizes["TopN"] == 0  # per-query fallback path

    st = ex.stacked_stats()
    assert st["batch_dispatches"] >= 4
    assert st["batched_queries"] >= 18 + 6 + 3


def test_batch_error_isolation(env):
    """One failing member (unknown field) reports its own error; every
    other member of the same batch still returns correct results."""
    holder, api, ex = env
    queries = ["Count(Row(f=1))", "Count(Row(nosuch=1))",
               "Count(Row(f=2))"]
    out = ex.execute_batch("i", queries)
    assert out[1][0] is None and out[1][1] is not None
    assert "nosuch" in str(out[1][1])
    for i in (0, 2):
        res, err, _, _ = out[i]
        assert err is None
        assert res[0] == ex.execute("i", queries[i])[0]


def test_batch_fallback_keyed_not_double_translated(env):
    """Fallback members re-execute from their UNTRANSLATED form. Key
    translation mutates the call tree in place and is not idempotent
    (the second pass sees an int where it demands a string key), so a
    batch member that falls back — non-batchable shape, or batchable
    but gather-missed on a single-shard index (< MIN_SHARDS) — must
    not be translated twice."""
    from pilosa_tpu.core.field import FieldOptions

    holder, api, ex = env
    api.create_index("kd")
    api.create_field("kd", "kf", FieldOptions(keys=True))
    api.query("kd", 'Set(7, kf="abc")')
    api.query("kd", 'Set(9, kf="abc")')
    # one shard only: Count(Row(kf="abc")) classifies batchable, gets
    # translated, then gather-misses (MIN_SHARDS) and falls back; TopN
    # exercises the never-batchable fallback on the same keyed field
    out = ex.execute_batch("kd", ['Count(Row(kf="abc"))', "TopN(kf)"])
    assert out[0][1] is None, out[0][1]
    assert out[1][1] is None, out[1][1]
    assert out[0][0] == ex.execute("kd", 'Count(Row(kf="abc"))')
    assert out[0][0] == [2]


def test_fused_dispatch_charged_once_in_workload(env):
    """N members riding ONE fused dispatch record 1 dispatch total in
    the workload table, not N — the path built to reduce dispatches
    must not inflate its own per-shape dispatch counts."""
    from pilosa_tpu.utils import workload as workload_mod

    holder, api, ex = env
    api.create_index("wk")
    api.create_field("wk", "f")
    cols = [s * SHARD_WIDTH + 3 for s in range(N_SHARDS)]
    api.import_bits("wk", "f", [0] * len(cols), cols)
    out = ex.execute_batch("wk", ["Count(Row(f=0))"] * 4)
    assert all(err is None for _, err, _, _ in out)
    assert {bsize for _, _, bsize, _ in out} == {4}
    snap = workload_mod.table().snapshot(top=100)
    mine = [e for e in snap["by_frequency"] if e["index"] == "wk"]
    assert mine
    assert sum(e["dispatches"] for e in mine) == 1


def test_batch_dispatch_flightrec_events(env):
    """Fused launches leave batch.dispatch events in the flight
    recorder (kernel family + occupancy + padded bucket)."""
    from pilosa_tpu.utils import flightrec

    holder, api, ex = env
    ex.execute_batch("i", ["Count(Row(f=0))", "Count(Row(f=1))"])
    events = [e for e in flightrec.snapshot()["events"]
              if e["kind"] == "batch.dispatch"]
    assert events
    last = events[-1]["tags"]
    assert last["queries"] == 2 and last["bucket"] == 4


# ------------------------------------------------------- coalescer layer


def test_coalescer_fuses_concurrent_queries(env):
    """Concurrent arrivals within the window fuse into one batched
    dispatch and every caller gets the serial path's exact answer."""
    holder, api, ex = env
    capi = API(holder, coalesce_window=0.005)
    want = {r: api.query("i", f"Count(Row(f={r}))")[0]
            for r in range(N_ROWS)}
    got, errs = {}, []

    def worker(r):
        try:
            got[r] = capi.query("i", f"Count(Row(f={r}))")[0]
        except Exception as e:  # noqa: BLE001 — surfaced via errs
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(r,))
          for r in range(N_ROWS)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    assert got == want
    st = capi.batching_stats()
    assert st["coalescer"]["enabled"]
    assert st["coalescer"]["coalesced_queries"] == N_ROWS
    assert st["coalescer"]["max_occupancy"] >= 2
    assert st["coalescer"]["queue_depth"] == 0

    # workload-table batch attribution followed the fused members
    from pilosa_tpu.utils import workload as workload_mod
    snap = workload_mod.table().snapshot(top=50)
    mine = [e for e in snap["by_frequency"] if e["index"] == "i"
            and e.get("batched_queries")]
    assert mine, "no workload entry carried batch attribution"
    assert any(e["avg_batch_size"] and e["avg_batch_size"] >= 2
               for e in mine)


def test_coalescer_ineligible_queries_use_legacy_path(env):
    """Non-batchable shapes (TopN, writes, multi-call, explain) fall
    through the coalescer to the legacy path and still work."""
    holder, api, ex = env
    capi = API(holder, coalesce_window=0.005)
    assert str(capi.query("i", "TopN(f, n=2)")[0]) == \
        str(api.query("i", "TopN(f, n=2)")[0])
    # multi-call requests keep their one-result-per-call contract
    multi = capi.query("i", "Count(Row(f=1)) Count(Row(f=2))")
    assert multi == [api.query("i", "Count(Row(f=1))")[0],
                     api.query("i", "Count(Row(f=2))")[0]]
    # parse errors surface as ApiError, same as the legacy path
    with pytest.raises(ApiError):
        capi.query("i", "Count(Row(f=")


def test_window_zero_is_legacy_path(env):
    """The default (window=0) builds NO coalescer; queries take the
    bit-identical pre-batching path."""
    holder, api, ex = env
    assert api._coalescer is None
    st = api.batching_stats()
    assert st["coalescer"]["enabled"] is False
    r = api.query("i", "Count(Row(f=3))")
    assert r == Executor(holder).execute("i", "Count(Row(f=3))")


def test_coalescer_overload_rejects_503(env):
    """A full coalesce queue rejects with 503 + Retry-After instead of
    queueing unboundedly, and counts the reject."""
    holder, api, ex = env
    capi = API(holder, coalesce_window=0.005, coalesce_max_queue=0)
    with pytest.raises(ServiceUnavailableError) as ei:
        capi.query("i", "Count(Row(f=1))")
    assert ei.value.status == 503
    assert ei.value.headers and "Retry-After" in ei.value.headers
    assert capi._coalescer.stats()["rejected"] == 1


def test_coalescer_survives_drain_loop_errors(env, monkeypatch):
    """An exception outside the guarded launch/resolve calls (here:
    flightrec.record, part of the loop's observability plumbing) is
    delivered to the waiting members — not left to kill the singleton
    drain thread, which would wedge every future submit forever — and
    the thread keeps serving subsequent queries."""
    from pilosa_tpu.utils import flightrec

    holder, api, ex = env
    capi = API(holder, coalesce_window=0.001)
    real, armed = flightrec.record, [True]

    def bad_record(kind, **tags):
        if armed[0] and kind == "batch.coalesce":
            armed[0] = False
            raise RuntimeError("observability exploded")
        return real(kind, **tags)

    monkeypatch.setattr(flightrec, "record", bad_record)
    with pytest.raises(ApiError, match="observability exploded"):
        capi.query("i", "Count(Row(f=1))")
    # same coalescer, same thread: the next query is served normally
    assert capi.query("i", "Count(Row(f=1))") == \
        api.query("i", "Count(Row(f=1))")
    capi.close()


def test_coalescer_close_unblocks_waiters(env):
    """close() never leaves a submit() hanging: queued members are
    delivered (results if their batch launched, 503 otherwise), new
    submits are refused with 503, and close is idempotent. API.close()
    on a window=0 deployment (no coalescer) is a no-op."""
    holder, api, ex = env
    api.close()  # window=0: must not raise
    capi = API(holder, coalesce_window=30.0)  # park members in-window
    done = []

    def worker():
        try:
            done.append(("ok", capi.query("i", "Count(Row(f=1))")))
        except Exception as e:  # noqa: BLE001 — surfaced via done
            done.append(("err", e))

    t = threading.Thread(target=worker)
    t.start()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline \
            and capi._coalescer._thread is None and not done:
        time.sleep(0.002)
    time.sleep(0.02)  # let the drain thread pop into its window wait
    capi.close()
    t.join(timeout=10)
    assert not t.is_alive(), "close() left a waiter hanging"
    assert done
    kind, val = done[0]
    if kind == "ok":  # batch launched before close: real results
        assert val == api.query("i", "Count(Row(f=1))")
    else:
        assert isinstance(val, ApiError)
    with pytest.raises(ServiceUnavailableError):
        capi._coalescer.submit("i", None, "Count(Row(f=1))")
    capi.close()  # idempotent


# ------------------------------------------------------------ HTTP layer


@pytest.fixture
def srv():
    s = ServerHarness()
    yield s
    s.close()


def _seed(srv):
    srv.client.create_index("i")
    srv.client.create_field("i", "f")
    cols = [s * SHARD_WIDTH + o for s in range(N_SHARDS)
            for o in (1, 5, 9)]
    srv.client.import_bits("i", "f", [1] * len(cols), cols)
    return cols


def test_http_query_batch_route(srv):
    """POST /index/{i}/query-batch: fused execution with per-slot
    results / errors, mixed batchable + non-batchable traffic."""
    _seed(srv)
    body = json.dumps({"queries": [
        "Count(Row(f=1))", "Row(f=1)", "TopN(f, n=1)",
        "Count(Row(bad=1))"]}).encode()
    out = srv.client._request("POST", "/index/i/query-batch", body)
    slots = out["results"]
    assert slots[0]["results"] == [3 * N_SHARDS]
    assert slots[1]["results"][0]["columns"] == \
        srv.client.query("i", "Row(f=1)")["results"][0]["columns"]
    assert "error" not in slots[2]  # non-batchable but still served
    assert "bad" in slots[3]["error"]
    # fused members carry their occupancy
    assert slots[0]["batch"] >= 1

    with pytest.raises(Exception):
        srv.client._request("POST", "/index/i/query-batch",
                            b'{"queries": "not-a-list"}')


def test_http_debug_batching(srv):
    """GET /debug/batching serves pipeline stats and is listed in the
    /debug index."""
    _seed(srv)
    srv.client._request(
        "POST", "/index/i/query-batch",
        json.dumps({"queries": ["Count(Row(f=1))"]}).encode())
    st = srv.client._request("GET", "/debug/batching")
    assert "coalescer" in st and "batch_dispatches" in st
    assert st["coalescer"]["enabled"] is False  # harness runs window=0
    paths = {e["path"] for e in
             srv.client._request("GET", "/debug")["endpoints"]}
    assert "/debug/batching" in paths


def test_slow_query_line_batch_attribution(srv):
    """SLOW QUERY lines carry batch= (and fused=) between fingerprint=
    and plan=; profile= stays LAST so existing parsers keep working.
    The coalesced path's line carries the member's own fingerprint even
    though end_query ran on the coalescer thread."""
    import re

    _seed(srv)
    log = CaptureLogger()
    srv.api.long_query_time = 0.0  # everything is slow
    srv.api.logger = log
    srv.client.query("i", "Count(Row(f=1))")
    line = [ln for ln in log.lines if "SLOW QUERY" in ln][-1]
    assert " batch=" in line
    assert re.search(
        r"fingerprint=([0-9a-f]{16}) batch=\d+ fused=\d+ plan=", line)
    # plan= field parsing (pinned by test_explain) is unchanged
    assert line.split(" plan=", 1)[1].split(" profile=", 1)[0] \
        == "Count=stacked"
    json.loads(line.split("profile=", 1)[1])

    # coalesced path: no profile (it runs on the coalescer thread), so
    # the short line format — fingerprint= then batch= last
    capi = API(srv.holder, coalesce_window=0.005,
               long_query_time=0.0, logger=log)
    capi.query("i", "Count(Row(f=1))")
    line2 = [ln for ln in log.lines if "SLOW QUERY" in ln][-1]
    m = re.search(r"fingerprint=([0-9a-f]{16}) batch=(\d+) fused=\d+$",
                  line2.strip())
    assert m, line2
    assert int(m.group(2)) >= 1


# -------------------------------------------------- observability plumbing


def test_plan_annotates_batched_strategy(env):
    """With a coalesce window configured, EXPLAIN marks stack-coverable
    Count/bitmap nodes `batched` and names the padding buckets."""
    from pilosa_tpu.exec import ExecOptions
    from pilosa_tpu.exec import plan as plan_mod

    holder, api, ex = env
    plan_mod.configure(coalesce_window=0.002)
    try:
        ex.execute("i", "Count(Row(f=1))",
                   options=ExecOptions(explain="plan"))
        env_plan = plan_mod.take_last()
        txt = json.dumps(env_plan)
        assert '"batched": true' in txt
        assert str(list(BATCH_BUCKETS)) \
            .replace(" ", "") in txt.replace(" ", "")
    finally:
        plan_mod.configure(coalesce_window=0.0)
    # window back to 0: fresh plans lose the annotation
    ex.execute("i", "Count(Row(f=1))",
               options=ExecOptions(explain="plan"))
    assert '"batched"' not in json.dumps(plan_mod.take_last())

"""Black-box surface: flight recorder ring, stall watchdog, HBM ledger
exactness, kernel attribution, and the /debug endpoints serving them
(ISSUE 4 acceptance: ledger total == _stack_bytes + _rows_stack_bytes
EXACTLY under randomized put/evict stress; a synthetic stuck dispatch
trips the watchdog and dumps the recorder tail + stacks)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import flightrec
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture(autouse=True)
def fresh_recorder():
    """Every test gets its own ring; the module default is restored."""
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)
    yield
    flightrec.stop_watchdog()
    flightrec.configure(flightrec.DEFAULT_RING_SIZE)


# ------------------------------------------------------------------- ring

def test_ring_records_and_snapshots():
    rec = flightrec.FlightRecorder(size=8)
    rec.record("dispatch.start", {"kernel": "count"})
    rec.record("dispatch.end", {"kernel": "count"})
    snap = rec.snapshot()
    assert snap["size"] == 8
    assert snap["total_events"] == 2
    assert snap["dropped"] == 0
    assert [e["kind"] for e in snap["events"]] == [
        "dispatch.start", "dispatch.end"]
    assert snap["events"][0]["tags"] == {"kernel": "count"}
    assert snap["events"][0]["seq"] == 1
    assert snap["events"][0]["ts"] <= time.time()


def test_ring_drops_oldest_and_counts():
    rec = flightrec.FlightRecorder(size=4)
    for i in range(10):
        rec.record("e", {"i": i})
    snap = rec.snapshot()
    assert snap["total_events"] == 10
    assert snap["dropped"] == 6
    assert rec.dropped == 6
    # oldest-first, only the newest 4 survive
    assert [e["tags"]["i"] for e in snap["events"]] == [6, 7, 8, 9]
    # limit trims from the tail end
    assert [e["tags"]["i"]
            for e in rec.snapshot(limit=2)["events"]] == [8, 9]


def test_disabled_recorder_is_inert():
    rec = flightrec.configure(0)
    assert not rec.enabled
    flightrec.record("x", a=1)  # must not raise, must not store
    assert flightrec.snapshot()["events"] == []
    assert flightrec.snapshot()["total_events"] == 0


def test_module_record_fast_path_and_tags():
    flightrec.record("cache.put", pool="stack", bytes=128)
    events = flightrec.snapshot()["events"]
    assert events[-1]["kind"] == "cache.put"
    assert events[-1]["tags"] == {"pool": "stack", "bytes": 128}


def test_ring_thread_safety_hammer():
    rec = flightrec.configure(256)
    n_threads, per_thread = 8, 500

    def pound(t):
        for i in range(per_thread):
            flightrec.record("hammer", thread=t, i=i)

    threads = [threading.Thread(target=pound, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = rec.snapshot()
    assert snap["total_events"] == n_threads * per_thread
    assert len(snap["events"]) == 256
    # seqs are unique and monotonically increasing
    seqs = [e["seq"] for e in snap["events"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_format_tail_and_stacks_are_strings():
    flightrec.record("x", a=1)
    tail = flightrec.get_recorder().format_tail()
    assert "flight recorder tail" in tail and "x a=1" in tail
    stacks = flightrec.format_all_stacks()
    assert "thread" in stacks and "test_flightrec" in stacks


# --------------------------------------------------------------- watchdog

def test_watchdog_trips_synthetic_stall():
    wd = flightrec.Watchdog(deadline=0.05)  # not started: check() driven
    token = wd.begin_op("dispatch.synthetic", index="i")
    assert wd.check() == []  # not yet overdue
    time.sleep(0.08)
    tripped = wd.check()
    assert len(tripped) == 1 and tripped[0].kind == "dispatch.synthetic"
    assert wd.stalls == 1
    # trips at most once per op
    assert wd.check() == []
    assert wd.stalls == 1
    wd.end_op(token)
    events = [e for e in flightrec.snapshot()["events"]
              if e["kind"] == "watchdog.stall"]
    assert len(events) == 1
    tags = events[0]["tags"]
    assert tags["kind"] == "dispatch.synthetic"
    assert tags["index"] == "i"
    assert tags["running_seconds"] >= 0.05


def test_watchdog_no_trip_inside_deadline():
    wd = flightrec.Watchdog(deadline=30.0)
    token = wd.begin_op("quick")
    assert wd.check() == []
    wd.end_op(token)
    time.sleep(0.02)
    assert wd.check() == [] and wd.stalls == 0


def test_watchdog_stall_dumps_tail_and_stacks():
    from pilosa_tpu.utils.logger import CaptureLogger

    log = CaptureLogger()
    wd = flightrec.Watchdog(deadline=0.01, logger=log)
    flightrec.record("breadcrumb", step=7)
    wd.begin_op("wedged")
    time.sleep(0.03)
    wd.check()
    dump = "\n".join(log.lines)
    assert "WATCHDOG STALL" in dump
    assert "flight recorder tail" in dump and "breadcrumb" in dump
    assert "thread" in dump  # all-thread stack dump rode along


def test_watchdog_thread_trips_without_manual_check():
    wd = flightrec.configure_watchdog(0.05)
    assert flightrec.get_watchdog() is wd
    token = flightrec.watch_begin("stuck_dispatch")
    assert token is not None
    deadline = time.time() + 5
    while not wd.stalls and time.time() < deadline:
        time.sleep(0.01)
    flightrec.watch_end(token)
    assert wd.stalls >= 1
    counters, _, _ = global_stats.snapshot()
    stall_keys = [k for k in counters if k[0] == "watchdog_stalls"]
    assert stall_keys
    flightrec.stop_watchdog()
    assert flightrec.get_watchdog() is None


def test_watch_begin_none_without_watchdog():
    flightrec.stop_watchdog()
    token = flightrec.watch_begin("anything")
    assert token is None
    flightrec.watch_end(token)  # must be a no-op, not a crash


def test_watchdog_rejects_bad_deadline():
    with pytest.raises(ValueError):
        flightrec.Watchdog(deadline=0)


# ----------------------------------------------------- HBM ledger exactness

def _ledger_pool_sums(ev):
    from pilosa_tpu.ops import containers

    sums = {}
    for pool_name, pool in (("stack", ev._stacks), ("rows", ev._rows_stacks)):
        for key, entry in pool.items():
            lkey = (key[1], key[2], pool_name, containers.kind_of(entry[1]))
            sums[lkey] = sums.get(lkey, 0) + entry[2]
    return sums


def _assert_ledger_exact(ev):
    assert ev._stack_bytes == sum(e[2] for e in ev._stacks.values())
    assert ev._rows_stack_bytes == sum(
        e[2] for e in ev._rows_stacks.values())
    snap = ev.hbm_snapshot(top=0)
    assert snap["total_bytes"] == ev._stack_bytes + ev._rows_stack_bytes
    assert sum(ev._hbm_ledger.values()) == snap["total_bytes"]
    assert dict(ev._hbm_ledger) == _ledger_pool_sums(ev)


def test_hbm_ledger_exact_under_randomized_stress(monkeypatch):
    """The acceptance invariant: /debug/hbm total bytes equals
    _stack_bytes + _rows_stack_bytes EXACTLY through thousands of
    randomized puts (fresh keys + replacements), budget evictions, and
    invalidations."""
    from pilosa_tpu.exec import stacked

    monkeypatch.setattr(stacked, "MAX_STACK_BYTES", 4096)
    monkeypatch.setattr(stacked, "MAX_ROWS_STACK_BYTES", 2048)
    ev = stacked.StackedEvaluator()
    rng = np.random.default_rng(99)
    indexes = ["i0", "i1", "i2"]
    fields = ["f0", "f1"]

    for step in range(2000):
        roll = rng.integers(0, 100)
        idx = indexes[int(rng.integers(0, len(indexes)))]
        fld = fields[int(rng.integers(0, len(fields)))]
        if roll < 2:
            ev.invalidate()
        elif roll < 50:
            key = ("leaf", idx, fld, int(rng.integers(0, 6)), (0, 1))
            ev._cache_put(key, (("g", step),), object(),
                          int(rng.integers(1, 900)), stamp=("s", step))
        else:
            key = ("rows", idx, fld, "standard",
                   int(rng.integers(0, 4)), (0, 1))
            ev._cache_put(key, (("g", step),), object(),
                          int(rng.integers(1, 600)), stamp=("s", step))
        if step % 50 == 0:
            _assert_ledger_exact(ev)
    _assert_ledger_exact(ev)
    # the stress must actually have exercised eviction + both pools
    assert ev.evictions > 0
    assert any(c == "budget" for (_, c) in ev.pool_evictions)


def test_eviction_counters_by_pool_and_cause(monkeypatch):
    from pilosa_tpu.exec import stacked

    monkeypatch.setattr(stacked, "MAX_STACK_BYTES", 1000)
    ev = stacked.StackedEvaluator()
    for i in range(4):
        ev._cache_put(("leaf", "i", "f", i, (0,)), ("g",), object(), 400)
    # 4 x 400 bytes under a 1000-byte budget: evictions happened
    assert ev.pool_evictions[("stack", "budget")] >= 1
    assert ev.cache_stats()["evictions_by_cause"]["stack.budget"] >= 1
    ev.invalidate()
    assert ev.pool_evictions[("stack", "invalidate")] >= 1
    assert ev._stack_bytes == 0 and ev._hbm_ledger == {}
    # cause-tagged counters reach the prometheus registry
    text = global_stats.prometheus_text()
    assert 'pilosa_tpu_stacked_evictions_total{' in text
    assert 'cause="budget"' in text and 'cause="invalidate"' in text
    # ledger gauges were zeroed, not dropped
    assert 'pilosa_tpu_hbm_stack_bytes{' in text


def test_cache_events_recorded(monkeypatch):
    from pilosa_tpu.exec import stacked

    monkeypatch.setattr(stacked, "MAX_STACK_BYTES", 500)
    ev = stacked.StackedEvaluator()
    ev._cache_put(("leaf", "idx", "fld", 1, (0,)), ("g",), object(), 400)
    ev._cache_put(("leaf", "idx", "fld", 2, (0,)), ("g",), object(), 400)
    kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
    assert kinds.count("cache.put") == 2
    assert "cache.evict" in kinds
    evict = [e for e in flightrec.snapshot()["events"]
             if e["kind"] == "cache.evict"][0]
    assert evict["tags"]["cause"] == "budget"
    assert evict["tags"]["index"] == "idx"


def test_replace_updates_ledger_without_eviction_count():
    from pilosa_tpu.exec import stacked

    ev = stacked.StackedEvaluator()
    key = ("leaf", "i", "f", 1, (0,))
    ev._cache_put(key, ("g1",), object(), 100)
    ev._cache_put(key, ("g2",), object(), 300)  # replacement
    assert ev.evictions == 0
    assert ev._stack_bytes == 300
    assert ev._hbm_ledger[("i", "f", "stack", "dense")] == 300


# ------------------------------------------------- kernel attribution

def test_note_kernel_and_snapshot():
    from pilosa_tpu.exec.stacked import StackedEvaluator

    ev = StackedEvaluator()
    ev._note_kernel("count", 0.01, 1024, 8)
    ev._note_kernel("count", 0.02, 1024, 8)
    snap = ev.kernels_snapshot(include_costs=False)
    k = snap["kernels"]["count"]
    assert k["count"] == 2
    assert k["seconds"] == pytest.approx(0.03)
    assert k["bytes_in"] == 2048 and k["bytes_out"] == 16
    assert "compiled" not in snap
    text = global_stats.prometheus_text()
    assert 'pilosa_tpu_kernel_seconds_count{kernel="count"}' in text
    assert 'pilosa_tpu_kernel_bytes_in_total{kernel="count"}' in text


def test_dispatch_instruments_kernels(tmp_path):
    """A real query through the executor attributes its dispatches and
    emits dispatch.start/end events with lock-wait/kernel-wall splits."""
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor

    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("ka")
        idx.create_field("f")
        # bits in 2 shards: the stacked path needs >= MIN_SHARDS
        idx.field("f").import_bits(
            np.array([1, 1, 1], dtype=np.uint64),
            np.array([5, 9, SHARD_WIDTH + 40], dtype=np.uint64))
        ex = Executor(holder)
        assert ex.execute("ka", "Count(Row(f=1))")[0] == 3
        st = ex._stacked
        kernels = st.kernels_snapshot(include_costs=False)["kernels"]
        assert "count" in kernels and kernels["count"]["count"] >= 1
        assert kernels["count"]["bytes_in"] > 0
        kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
        assert "dispatch.start" in kinds and "dispatch.end" in kinds
        end = [e for e in flightrec.snapshot()["events"]
               if e["kind"] == "dispatch.end"][-1]
        assert end["tags"]["kernel"] == "count"
        assert end["tags"]["kernel_wall_seconds"] >= 0
        # cost analysis: lazily computed, cached, never raises
        compiled = st.kernels_snapshot()["compiled"]
        assert isinstance(compiled, list) and compiled
        assert all("family" in c and "cost" in c for c in compiled)
    finally:
        holder.close()


def test_hbm_snapshot_entries_after_query(tmp_path):
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec import Executor

    holder = Holder(str(tmp_path)).open()
    try:
        idx = holder.create_index("hb")
        idx.create_field("f")
        idx.field("f").import_bits(
            np.array([2, 2], dtype=np.uint64),
            np.array([1, SHARD_WIDTH + 7], dtype=np.uint64))
        ex = Executor(holder)
        ex.execute("hb", "Count(Row(f=2))")
        snap = ex._stacked.hbm_snapshot()
        assert snap["total_bytes"] > 0
        assert snap["total_bytes"] == \
            snap["stack_bytes"] + snap["rows_stack_bytes"]
        entry = snap["entries"][0]
        assert entry["index"] == "hb" and entry["field"] == "f"
        assert entry["bytes"] > 0
        assert entry["last_hit_age_seconds"] >= 0
        assert snap["by_index_field"][0]["index"] == "hb"
    finally:
        holder.close()


# ------------------------------------------------------- /debug endpoints

@pytest.fixture
def harness(tmp_path):
    from tests.harness import ServerHarness

    h = ServerHarness(data_dir=str(tmp_path))
    yield h
    h.close()


def _warm_query(h):
    h.client.create_index("dbg")
    h.client.create_field("dbg", "f")
    h.client.query("dbg", "Set(3, f=11)")
    h.client.query("dbg", f"Set({SHARD_WIDTH + 5}, f=11)")  # 2nd shard
    h.client.query("dbg", "Count(Row(f=11))")


def test_debug_flightrecorder_endpoint(harness):
    _warm_query(harness)
    snap = harness.client.debug_flightrecorder()
    assert snap["size"] == flightrec.get_recorder().size
    kinds = [e["kind"] for e in snap["events"]]
    assert "dispatch.start" in kinds
    limited = harness.client.debug_flightrecorder(limit=1)
    assert len(limited["events"]) == 1


def test_debug_hbm_endpoint(harness):
    _warm_query(harness)
    snap = harness.client.debug_hbm(top=3)
    assert snap["total_bytes"] == \
        snap["stack_bytes"] + snap["rows_stack_bytes"]
    assert snap["total_bytes"] > 0
    assert len(snap["entries"]) <= 3
    assert snap["entries"][0]["index"] == "dbg"
    assert "evictions" in snap and "device_memory" in snap


def test_debug_kernels_endpoint(harness):
    _warm_query(harness)
    snap = harness.client.debug_kernels(costs=False)
    assert "count" in snap["kernels"]
    assert "compiled" not in snap
    full = harness.client.debug_kernels()
    assert isinstance(full.get("compiled"), list)


def test_status_carries_local_observability(harness):
    _warm_query(harness)
    status = harness.client.status()
    obs = status["observability"]
    node = obs["local"]
    assert node["hbm"]["total_bytes"] > 0
    assert "count" in node["kernels"]
    assert node["kernels"]["count"]["count"] >= 1


def test_http_5xx_records_event(harness):
    def boom():
        raise RuntimeError("kaboom")

    harness.api.schema = boom
    with pytest.raises(Exception):
        harness.client.schema()
    # the handler thread records AFTER writing the response; poll briefly
    events = []
    deadline = time.time() + 5
    while not events and time.time() < deadline:
        events = [e for e in flightrec.snapshot()["events"]
                  if e["kind"] == "http.5xx"]
        if not events:
            time.sleep(0.01)
    assert events
    assert events[-1]["tags"]["status"] >= 500


# -------------------------------------------------------- stats satellite

def test_runtime_monitor_sample_age_gauge():
    from pilosa_tpu.utils.stats import RuntimeMonitor, StatsClient

    stats = StatsClient()
    mon = RuntimeMonitor(stats, interval=60)
    mon.start()
    try:
        _, gauges, _ = stats.snapshot()
        key = ("runtime_monitor_last_sample_age_seconds", ())
        assert key in gauges
        assert 0 <= gauges[key] < 5
        # scrape-time evaluation: the age grows between snapshots even
        # though the sampler thread never runs again
        mon.last_sample_time = time.time() - 120
        _, gauges, _ = stats.snapshot()
        assert gauges[key] >= 119
        assert "runtime_monitor_last_sample_age_seconds" \
            in stats.prometheus_text()
    finally:
        mon.stop()


def test_gauge_fn_errors_do_not_break_snapshot():
    from pilosa_tpu.utils.stats import StatsClient

    stats = StatsClient()
    stats.gauge("ok", 1)
    stats.gauge_fn("bad", lambda: 1 / 0)
    _, gauges, _ = stats.snapshot()
    assert gauges[("ok", ())] == 1
    assert ("bad", ()) not in gauges


# ------------------------------------------------------------ crash handler

def test_crash_handler_dumps_on_sigterm():
    import subprocess
    import sys

    code = r"""
import os, signal, sys
sys.path.insert(0, %r)
from pilosa_tpu.utils import flightrec
flightrec.record("last.breadcrumb", step=42)
flightrec.install_crash_handler()
signal.raise_signal(signal.SIGTERM)
""" % (str(__import__("pathlib").Path(__file__).resolve().parents[1]),)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60)
    assert proc.returncode != 0  # the chained default handler still kills
    assert "flightrec dump (SIGTERM)" in proc.stderr
    assert "last.breadcrumb" in proc.stderr

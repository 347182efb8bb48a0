"""The stages of a query where the work happens (ISSUE 26): the group
commit's queue, the stack cache's patch, the names of the jitted programs,
and the benchmark's readers of the spans and counters behind them."""

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
from pilosa_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
READ_CELL, RW_CELL = "seg1b-read-c32", "seg1b-rw-c32"
QUERY_ROUTE = "/index/(?P<index>[^/]+)/query"


@pytest.fixture
def tracer():
    t = tracing.InMemoryTracer()
    tracing.set_tracer(t)
    yield t
    tracing.set_tracer(None)


# ------------------------------------------------------------ dispatch.queue


def test_group_commit_follower_gets_a_queue_span(tracer):
    """A follower's whole wait is `dispatch.queue` role=follower, tagged
    with the batch it rode; the caller that leads that batch waited in
    the same queue, role=leader. The first leader is held in `process` by
    an event until both have queued behind it."""
    from pilosa_tpu.exec.stacked import GroupCommit

    commit = GroupCommit()
    processing, release = threading.Event(), threading.Event()

    def process(payloads):
        processing.set()
        release.wait(30)
        return [p * 2 for p in payloads]

    results = {}

    def submit(name, payload):
        with tracing.start_span(name):
            results[name] = commit.submit(payload, process)

    threads = [threading.Thread(target=submit, args=("first", 1))]
    threads[0].start()
    assert processing.wait(30)
    for n, name in enumerate(("second", "third"), 1):
        threads.append(threading.Thread(target=submit, args=(name, n + 1)))
        threads[-1].start()
        while len(commit._queue) < n:
            time.sleep(0.001)
    assert not tracer.find("second")  # still waiting for the batch in flight
    release.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    assert results == {"first": 2, "second": 4, "third": 6}
    by_parent = {s.parent_id: s for s in tracer.find("dispatch.queue")}
    tags = {name: by_parent[tracer.find(name)[0].span_id].tags
            for name in results}
    assert tags == {"first": {"role": "leader", "batch": 1},
                    "second": {"role": "leader", "batch": 2},
                    "third": {"role": "follower", "batch": 2}}
    assert (commit.batches, commit.batched) == (2, 3)


# -------------------------------------------------------------- stack.lookup


def test_lookup_after_a_write_says_patch(tmp_path):
    """build, then hit; after a write to one shard the reader's lookup of
    the written field patches one plane and the other field's still hits."""
    from pilosa_tpu.core import Holder
    from pilosa_tpu.exec.executor import ExecOptions
    from pilosa_tpu.server.api import API

    holder = Holder(str(tmp_path), cache_flush_interval=0).open()
    try:
        api = API(holder)
        api.create_index("p")
        for field in ("f", "g"):
            api.create_field("p", field)
            api.import_bits("p", field, [1] * 4,
                            [s * SHARD_WIDTH + 5 for s in range(4)])
        pql = "Count(Intersect(Row(f=1), Row(g=1)))"

        def lookups():
            profile_mod.take_last()
            assert api.query("p", pql, options=ExecOptions(profile=True))
            found = []

            def walk(node):
                if node["name"] == "stack.lookup":
                    found.append((node["tags"]["field"],
                                  node["tags"]["outcome"],
                                  node["tags"]["planes_uploaded"]))
                for child in node["children"]:
                    walk(child)

            walk(profile_mod.take_last()["spans"])
            return found

        assert lookups() == [("f", "build", 4), ("g", "build", 4)]
        assert lookups() == [("f", "hit", 0), ("g", "hit", 0)]
        api.import_bits("p", "f", [1], [2 * SHARD_WIDTH + 9])
        assert lookups() == [("f", "patch", 1), ("g", "hit", 0)]
        assert lookups() == [("f", "hit", 0), ("g", "hit", 0)]
    finally:
        holder.close()


# ------------------------------------------------------ the programs' names


SIG = ("&", (("leaf", 0), ("leaf", 1)))
PROGRAMS = {  # PERF.md section 3: builder -> the name its program carries
    "count_tree": lambda ev: ev._count_fn(SIG, 2),
    "count_batch": lambda ev: types.SimpleNamespace(
        _jit_fn=ev._count_batch_fn((None, SIG[1]), 2, 2)),
    "fused_count": lambda ev: ev.fused_count_fn(((SIG, 2),))[0],
    "plane_tree": lambda ev: ev._plane_fn(SIG, 2),
    "row_counts": lambda ev: ev._row_counts_fn(True),
    "bsi_sum": lambda ev: ev._sum_fn(False),
    "bsi_minmax": lambda ev: ev._minmax_fn(True, True),
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_jitted_program_carries_its_own_name(name):
    from pilosa_tpu.exec.stacked import StackedEvaluator

    fn = PROGRAMS[name](StackedEvaluator())
    assert fn._jit_fn.__name__ == name


# ----------------------------------------------- the benchmark's new readers


@pytest.fixture(scope="module")
def bench():
    """benchmark/harness, importable as the benchmark's own files import
    it (`from harness import ...`)."""
    sys.path.insert(0, BENCH)
    try:
        from harness import cell, manifest
        yield types.SimpleNamespace(cell=cell, manifest=manifest)
    finally:
        sys.path.remove(BENCH)


def _context(bench, before=None, after=None, profiles=()):
    run = types.SimpleNamespace(
        cell={}, config={}, spec={}, device={}, opened=0.0, closed=40.0,
        t0=-50.0, window=[], profiles=list(profiles),
        before={"vars": before or {}}, after={"vars": after or {}},
        trace=None, traced_queries=[])
    return bench.cell.Context(run)


def _row(count, self_cpu):
    return {"count": count, "seconds": 9.0, "self_seconds": 9.0,
            "cpu_seconds": 9.0, "self_cpu_seconds": self_cpu}


def _made_up(bench):
    """A window of 100 profiled queries over counters that were already
    running: every number below is by hand."""
    names = ["api.Query", "pql.parse", "exec.translate", "executor.Execute",
             "executor.executeCount", "exec.plan", "stack.lookup",
             "dispatch.queue", "dispatch.lock_wait", "stacked.kernel",
             "dispatch.fetch", "dispatch.account"]
    before = {"spans": {n: _row(10, 1.0) for n in names},
              "process": {"cpu_seconds": 50.0},
              "holder": {"cache_flushes": 1, "cache_flush_seconds": 20.0},
              "timings": {}}
    gained = dict(zip(names, [0.020, 0.010, 0.005, 0.004, 0.030, 0.016,
                              0.007, 0.001, 0.002, 0.040, 0.009, 0.003]))
    after = {"spans": {n: _row(110 if n not in ("stack.lookup",
                                                "exec.translate") else 210,
                               1.0 + gained[n]) for n in names},
             "process": {"cpu_seconds": 50.3},
             "holder": {"cache_flushes": 2, "cache_flush_seconds": 31.5},
             "timings": {}}
    key = f"http_request_seconds{{method=POST,route={QUERY_ROUTE},status=200}}"
    before["timings"][key] = {"count": 20, "sum": 1.0}
    after["timings"][key] = {"count": 140, "sum": 9.0}
    profiles = [{"dispatch.lock_wait": [w / 1e3, w / 1e3],
                 "dispatch.fetch": [2 * w / 1e3, 2 * w / 1e3],
                 "stack.lookup": [3 * w / 1e3, 3 * w / 1e3],
                 "exec.plan": [w / 1e3, w / 1e3],
                 "dispatch.account": [w / 2e3, w / 2e3],
                 "executor.Execute": [10 * w / 1e3, 0.0]}
                for w in (1.0, 2.0, 3.0, 4.0, 50.0)] + [{"api.Query": [1, 1]}]
    return _context(bench, before, after, profiles)


EXPECT = {  # metric -> its value on _made_up
    "proc.cpu_ms_per_query": 0.3 / 120 * 1e3,
    "api.cpu_ms": (0.020 + 0.010 + 0.005) / 100 * 1e3,
    "exec.cpu_ms": (0.004 + 0.030 + 0.016) / 100 * 1e3,
    "stack.cpu_ms": 0.007 / 100 * 1e3,
    "dispatch.cpu_ms": (0.001 + 0.002 + 0.040 + 0.009 + 0.003) / 100 * 1e3,
    "dispatch.lock_wait_ms": 3.0,
    "dispatch.fetch_ms": 6.0,
    "stack.lookup_ms": 9.0,
    "flush.seconds_in_window": 11.5,
    "exec.stage_cover": 75.0,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_of_a_new_metric(bench, name):
    """None where the span or counter is absent (a parent commit, a cell
    that never takes the path), else the number computed by hand; the
    `.rw` name is read by the same file."""
    read = bench.manifest.reader(name)
    assert read(_context(bench)) is None
    assert read(_made_up(bench)) == pytest.approx(EXPECT[name])
    if name != "stack.lookup_ms":
        assert bench.manifest.reader_path(name + ".rw") \
            == bench.manifest.reader_path(name)


def test_new_metrics_are_entered_for_their_cells(bench):
    per_layer = {m["name"]: m for m in bench.manifest.load()["per_layer"]}
    for name in EXPECT:
        if name == "stack.lookup_ms":
            assert per_layer[name]["workloads"] == [RW_CELL]
            assert per_layer[name]["moves"] == "write_ack_p50_ms"
            continue
        assert READ_CELL in per_layer[name]["workloads"]
        assert per_layer[name]["moves"] == "query_qps"
        assert per_layer[name + ".rw"]["workloads"] == [RW_CELL]
        assert per_layer[name + ".rw"]["moves"] == "write_ack_p50_ms"
        assert per_layer[name]["better"] == (
            "higher" if name == "exec.stage_cover" else "lower")
    # what was there is still entered and still read
    for name in ("exec.self_ms", "api.self_ms", "exec.self_ms.rw"):
        assert callable(bench.manifest.reader(name))


@pytest.mark.parametrize("rule", [
    "test_names_and_units", "test_every_named_file_exists",
    "test_cells_report_what_their_metrics_move"])
def test_manifest_rules_hold_with_the_new_entries(bench, rule):
    """The three manifest rules of benchmark/tests/test_harness.py."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_test_harness",
        os.path.join(BENCH, "tests", "test_harness.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    getattr(module, rule)(bench.manifest.load())
    assert len(json.dumps(bench.manifest.load())) < 64 * 1024

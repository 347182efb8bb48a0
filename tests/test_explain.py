"""PQL EXPLAIN/ANALYZE (exec/plan.py): plan-tree shape per op family vs
the executor's actual strategy choices, zero-dispatch planning, analyze
grafting, misestimate flagging + the /debug/plans ring, cluster sub-plan
aggregation, and the HTTP/CLI surface.

The acceptance contract (ISSUE 5): ?explain=true on Intersect+Count and a
two-field GroupBy returns a plan tree naming the chosen strategy with
per-node cost estimates and ZERO device dispatches; ?explain=analyze
attaches actual wall/dispatch/bytes per node, flagging >factor deviations.
"""

import json

import numpy as np
import pytest

from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.exec import Executor
from pilosa_tpu.exec import plan as plan_mod
from pilosa_tpu.exec.executor import ExecOptions
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import profile as profile_mod
from pilosa_tpu.utils.logger import CaptureLogger
from tests.harness import ClusterHarness, ServerHarness

N_SHARDS = 3  # >= MIN_SHARDS so stacked strategies are eligible


@pytest.fixture
def env(tmp_path):
    h = Holder(str(tmp_path / "data"), use_snapshot_queue=False).open()
    idx = h.create_index("i")
    idx.create_field("a")
    idx.create_field("b")
    idx.create_field("v", FieldOptions.int_field(min=0, max=1000))
    cols = [s * SHARD_WIDTH + off
            for s in range(N_SHARDS) for off in (0, 3, 7, 11, 19)]
    idx.field("a").import_bits([i % 3 for i in range(len(cols))], cols)
    idx.field("b").import_bits([i % 2 for i in range(len(cols))], cols)
    idx.field("v").import_values(cols, [(i * 37) % 1000
                                        for i in range(len(cols))])
    e = Executor(h)
    yield h, e
    h.close()


def plan_of(e, pql, mode="plan"):
    out = e.execute("i", pql, options=ExecOptions(explain=mode))
    env = plan_mod.take_last()
    assert env is not None, "executor stashed no plan envelope"
    return out, env


def walk(d):
    yield d
    for c in d.get("children", []):
        if isinstance(c, dict):
            yield from walk(c)


# ------------------------------------------------ zero-dispatch planning


def test_explain_plan_zero_dispatch_intersect_count(env):
    """Acceptance: explain=true on Intersect+Count plans without a single
    device dispatch and names the stacked strategy with estimates."""
    h, e = env
    d0 = e._stacked.cache_stats()["dispatches"]
    out, penv = plan_of(e, "Count(Intersect(Row(a=1), Row(b=1)))")
    assert out == []
    assert e._stacked.cache_stats()["dispatches"] == d0, \
        "explain=plan dispatched to the device"

    assert penv["mode"] == "plan"
    assert penv["index"] == "i"
    top = penv["calls"][0]
    assert top["op"] == "Count"
    assert top["strategy"] == "stacked"
    assert top["estimate"]["dispatches"] == 1
    assert top["estimate"]["kernels"] == {"count": 1}
    assert top["estimate"]["kernel_wall_seconds"] >= 0
    assert top["estimate"]["cost_source"] in (
        "measured", "histogram", "xla", "default")
    assert top["annotations"]["cache"] in ("cold", "warm", "partial")
    # full recursive tree under the aggregate
    inter = top["children"][0]
    assert inter["op"] == "Intersect"
    assert inter["strategy"] == "per-shard-planes"
    assert inter["annotations"]["stack_coverable"] is True
    assert [c["op"] for c in inter["children"]] == ["Row", "Row"]


def test_explain_plan_zero_dispatch_pairwise_groupby(env):
    """Acceptance: explain=true on a two-field GroupBy names the pairwise
    strategy with its tile shape — and still dispatches nothing."""
    h, e = env
    d0 = e._stacked.cache_stats()["dispatches"]
    out, penv = plan_of(e, "GroupBy(Rows(a), Rows(b))")
    assert out == []
    assert e._stacked.cache_stats()["dispatches"] == d0

    top = penv["calls"][0]
    assert top["op"] == "GroupBy"
    assert top["strategy"] == "stacked-pairwise"
    ann = top["annotations"]
    assert ann["rows_per_field"] == [3, 2]
    assert ann["tile"] == [3, 2]
    assert ann["pairwise_tiles"] == [1, 1]
    assert ann["outer_combinations"] == 1
    assert top["estimate"]["pairwise_dispatches"] == 1
    assert top["estimate"]["dispatches"] == 1
    # each Rows child planned as host metadata
    assert [c["strategy"] for c in top["children"][:2]] == \
        ["host-metadata", "host-metadata"]


# -------------------------------------------- plan shape per op family


def test_plan_strategy_oracle_per_op_family(env):
    """Every PQL op family plans the strategy a naive reading of the
    executor's gates predicts for this (multi-shard, coverable) index."""
    h, e = env
    oracle = [
        ("Row(a=1)", "Row", "per-shard-planes"),
        ("Intersect(Row(a=1), Row(b=1))", "Intersect", "per-shard-planes"),
        ("Union(Row(a=1), Row(b=1))", "Union", "per-shard-planes"),
        ("Count(Row(a=1))", "Count", "stacked"),
        ("Count(Union(Row(a=1), Row(b=0)))", "Count", "stacked"),
        ("TopN(a, n=2)", "TopN", "stacked-row-counts"),
        ("Sum(field=v)", "Sum", "stacked-sum"),
        ("Min(field=v)", "Min", "stacked-minmax"),
        ("Max(field=v)", "Max", "stacked-minmax"),
        ("Count(Row(v > 5))", "Count", "stacked"),  # Range-BSI
        ("Rows(a)", "Rows", "host-metadata"),
        ("GroupBy(Rows(a))", "GroupBy", "stacked-row-counts"),
        ("GroupBy(Rows(a), Rows(b))", "GroupBy", "stacked-pairwise"),
        ("MinRow(field=a)", "MinRow", "per-shard-scan"),
    ]
    for pql, op, strategy in oracle:
        _, penv = plan_of(e, pql)
        top = penv["calls"][0]
        assert (top["op"], top["strategy"]) == (op, strategy), pql
        est = top["estimate"]
        assert "cost_source" in est and "kernel_wall_seconds" in est, pql

    # Range-BSI condition: the gather itself issues a bsi_condition
    # kernel, so the estimate prices 2 dispatches, not 1
    _, penv = plan_of(e, "Count(Row(v > 5))")
    est = penv["calls"][0]["estimate"]
    assert est["dispatches"] == 2
    assert est["kernels"].get("bsi_condition") == 1


def test_plan_falls_back_under_min_shards(env):
    """Options(shards=[0]) narrows below MIN_SHARDS: the wrapped Count
    plans per-shard and says why."""
    h, e = env
    _, penv = plan_of(e, "Options(Count(Row(a=0)), shards=[0])")
    top = penv["calls"][0]
    assert top["strategy"] == "option-wrapper"
    inner = top["children"][0]
    assert inner["strategy"] == "per-shard"
    assert "MIN_SHARDS" in inner["reason"]
    assert top["estimate"]["dispatches"] == 0


def test_plan_mirrors_executor_validation(env):
    """Planning rejects what execution rejects, with the same error."""
    from pilosa_tpu.exec import ExecError

    h, e = env
    for pql in ("GroupBy(Row(a=1))",
                "Options(Count(Row(a=0)), banana=1)"):
        with pytest.raises(ExecError):
            e.execute("i", pql, options=ExecOptions(explain="plan"))


# ---------------------------------------------------- analyze grafting


def test_analyze_grafts_actuals_and_matches_estimates(env):
    """explain=analyze executes (correct results!), grafts measured
    counters per top-level node, and the dispatch estimate is exact."""
    h, e = env
    want = e.execute("i", "Count(Intersect(Row(a=1), Row(b=1)))")[0]
    out, penv = plan_of(e, "Count(Intersect(Row(a=1), Row(b=1)))",
                        mode="analyze")
    assert out == [want]
    assert penv["mode"] == "analyze"
    assert "misestimates" in penv
    top = penv["calls"][0]
    act = top["actual"]
    assert act["wall_seconds"] > 0
    assert act["dispatches"] == top["estimate"]["dispatches"] == 1
    assert act["strategy"] == top["strategy"] == "stacked"
    assert act["kernels"].get("count") == 1


def test_analyze_dispatch_estimates_exact_across_ops(env):
    """Estimated dispatches == actual dispatches for every stacked
    strategy (the cost model mirrors the real gates, not heuristics)."""
    h, e = env
    for pql in ("GroupBy(Rows(a), Rows(b))", "TopN(a, n=2)",
                "Sum(field=v)", "Count(Row(v > 5))"):
        _, penv = plan_of(e, pql, mode="analyze")
        top = penv["calls"][0]
        assert top["actual"]["dispatches"] == \
            top["estimate"]["dispatches"], pql


def test_misestimate_flagging_and_ring(env, monkeypatch):
    """A wildly wrong estimate flags the node, ticks the counter, and
    retains the envelope in the /debug/plans ring."""
    h, e = env
    plan_mod.clear_recent()
    flagged0 = plan_mod.stats()["misestimates_flagged"]
    # force a 1000x kernel-wall overestimate regardless of what the
    # process's histograms have learned
    monkeypatch.setattr(plan_mod.CostModel, "dispatch_seconds",
                        lambda self, family: (100.0, "default"))
    _, penv = plan_of(e, "Count(Intersect(Row(a=1), Row(b=1)))",
                      mode="analyze")
    top = penv["calls"][0]
    assert top["misestimates"], "100s/dispatch estimate was not flagged"
    flag = top["misestimates"][0]
    assert flag["metric"] == "kernel_wall_seconds"
    assert flag["deviation"] > plan_mod.misestimate_factor()
    assert penv["misestimates"] >= 1

    assert plan_mod.stats()["misestimates_flagged"] == flagged0 + 1
    retained = plan_mod.recent()
    assert retained and retained[0]["calls"][0]["op"] == "Count"
    plan_mod.clear_recent()


def test_accurate_analyze_not_retained(env):
    """Plans whose estimates hold are NOT retained — the ring is a
    misestimate debugger, not a query log."""
    h, e = env
    pql = "Count(Row(a=1))"
    e.execute("i", pql)  # warm: kernel measured, caches resident
    plan_mod.clear_recent()
    _, penv = plan_of(e, pql, mode="analyze")
    if not penv["calls"][0]["misestimates"]:
        assert plan_mod.recent() == []
    plan_mod.clear_recent()


def test_flag_misestimates_unit():
    """Deviation semantics: symmetric, floored, one flag per metric."""
    node = plan_mod.PlanNode("Count", strategy="stacked")
    node.estimate = {"kernel_wall_seconds": 0.010, "dispatches": 1,
                     "bytes_materialized": 0}
    node.actual = {"kernel_wall_seconds": 0.100, "dispatches": 1,
                   "bytes_materialized": 0}
    plan_mod.flag_misestimates(node, factor=3.0)
    assert [f["metric"] for f in node.misestimates] == \
        ["kernel_wall_seconds"]
    assert node.misestimates[0]["deviation"] == 10.0

    # both sides under the floor: not flagged even at huge ratios
    node2 = plan_mod.PlanNode("Count")
    node2.estimate = {"kernel_wall_seconds": 1e-9}
    node2.actual = {"kernel_wall_seconds": 1e-6}
    plan_mod.flag_misestimates(node2, factor=3.0)
    assert node2.misestimates == []

    # overestimates flag exactly like underestimates (symmetric)
    node3 = plan_mod.PlanNode("Count")
    node3.estimate = {"dispatches": 40}
    node3.actual = {"dispatches": 2}
    plan_mod.flag_misestimates(node3, factor=3.0)
    assert node3.misestimates[0]["deviation"] == 20.0


def test_ring_configure_bounds():
    plan_mod.clear_recent()
    old = plan_mod.stats()["ring_size"]
    try:
        plan_mod.configure(ring_size=3)
        for i in range(7):
            plan_mod.record({"index": f"r{i}", "mode": "analyze",
                             "calls": []})
        got = plan_mod.recent()
        assert len(got) == 3
        assert got[0]["index"] == "r6"  # newest first
        assert plan_mod.recent(limit=1) == [got[0]]
    finally:
        plan_mod.configure(ring_size=old)
        plan_mod.clear_recent()


def test_summary_marks_misestimated_nodes():
    n1 = plan_mod.PlanNode("Count", strategy="stacked")
    n2 = plan_mod.PlanNode("GroupBy", strategy="stacked-pairwise")
    n2.misestimates = [{"metric": "dispatches"}]
    assert plan_mod.summary([n1, n2]) == \
        "Count=stacked,GroupBy=stacked-pairwise!"


# ------------------------------------------------------- HTTP surface


def test_http_explain_param_and_debug_plans(tmp_path, monkeypatch):
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        h.client.create_index("hx")
        h.client.create_field("hx", "f")
        cols = [s * SHARD_WIDTH + o for s in range(N_SHARDS)
                for o in (1, 5)]
        h.client.import_bits("hx", "f", [1] * len(cols), cols)

        # ?explain=true: plan attached, nothing executed
        resp = h.client.query("hx", "Count(Row(f=1))", explain="true")
        assert resp["results"] == []
        assert resp["plan"]["mode"] == "plan"
        assert resp["plan"]["calls"][0]["strategy"] == "stacked"

        # ?explain=analyze: results AND plan with actuals
        resp = h.client.query("hx", "Count(Row(f=1))", explain="analyze")
        assert resp["results"] == [len(cols)]
        top = resp["plan"]["calls"][0]
        assert top["actual"]["dispatches"] >= 1

        # bad value is a 400, named clearly
        from pilosa_tpu.server import ClientError

        with pytest.raises(ClientError) as ei:
            h.client.query("hx", "Count(Row(f=1))", explain="banana")
        assert ei.value.status == 400
        assert "explain" in str(ei.value)

        # force a retained plan, then read it back over the debug route
        plan_mod.clear_recent()
        monkeypatch.setattr(plan_mod.CostModel, "dispatch_seconds",
                            lambda self, family: (100.0, "default"))
        h.client.query("hx", "Count(Row(f=1))", explain="analyze")
        out = h.client.debug_plans()
        assert out["retained"] >= 1
        assert out["misestimates_flagged"] >= 1
        assert out["plans"][0]["calls"][0]["misestimates"]
        # limit=0: counters only (the coordinator roll-up shape)
        out0 = h.client.debug_plans(limit=0)
        assert out0["plans"] == [] and out0["retained"] >= 1

        # plan counters roll up into /status node observability
        status = h.client._request("GET", "/status")
        summaries = status.get("observability", {})
        assert summaries, "/status carried no observability section"
        local = next(iter(summaries.values()))
        assert local["plans"]["retained"] >= 1
        assert local["plans"]["misestimates_flagged"] >= 1
        plan_mod.clear_recent()
    finally:
        h.close()


def test_slow_query_log_carries_plan_and_trace(tmp_path):
    """SLOW QUERY lines gain trace= and plan= fields; profile= stays the
    LAST field so existing json parsing keeps working."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        log = CaptureLogger()
        h.api.long_query_time = 0.0  # everything is slow
        h.api.logger = log
        profile_mod.clear_recent()
        h.client.create_index("sq")
        h.client.create_field("sq", "f")
        cols = [s * SHARD_WIDTH + o for s in range(N_SHARDS)
                for o in (1, 5)]
        h.client.import_bits("sq", "f", [1] * len(cols), cols)
        h.client.query("sq", "Count(Row(f=1))")

        slow = [ln for ln in log.lines if "SLOW QUERY" in ln]
        assert slow
        line = slow[-1]
        assert " trace=" in line and " plan=" in line
        # the plan summary names the strategy the executor chose
        plan_field = line.split(" plan=", 1)[1].split(" profile=", 1)[0]
        assert plan_field == "Count=stacked"
        trace_field = line.split(" trace=", 1)[1].split(" ", 1)[0]
        # the embedded profile still parses AND carries the same trace id
        tree = json.loads(line.split("profile=", 1)[1])
        assert tree["spans"]["name"] == "query"
        assert tree["traceID"] == trace_field

        # analyze summaries flag misestimated ops with "!"
        h.client.query("sq", "Count(Row(f=1))", explain="analyze")
        slow2 = [ln for ln in log.lines if "SLOW QUERY" in ln][-1]
        plan_field2 = slow2.split(" plan=", 1)[1].split(" profile=", 1)[0]
        assert plan_field2.startswith("Count=stacked")
    finally:
        h.close()


# ------------------------------------------------------ cluster fan-out


def test_cluster_plan_embeds_per_node_subplans():
    import time

    from pilosa_tpu.cluster import ModHasher

    # deterministic placement: shards alternate owners, so BOTH the
    # local-planner leg and the remote explain fan-out leg run
    h = ClusterHarness(2, replica_n=1, hasher=ModHasher())
    try:
        h[0].client.create_index("ce")
        h[0].client.create_field("ce", "f")
        time.sleep(0.3)  # DDL broadcast settles
        n_shards = 6
        cols = [s * SHARD_WIDTH + 2 for s in range(n_shards)]
        h[0].client.import_bits("ce", "f", [1] * len(cols), cols)

        # explain=true: coordinator node wraps one sub-plan per owner,
        # nothing executes anywhere
        resp = h[0].client.query("ce", "Count(Row(f=1))", explain="true")
        assert resp["results"] == []
        penv = resp["plan"]
        assert penv["mode"] == "plan"
        top = penv["calls"][0]
        assert top["strategy"] == "cluster-map-reduce"
        children = top["children"]
        # one sub-plan per PRIMARY owner (jump hash may not use both
        # nodes for a small shard count — derive the truth from it)
        owners = {h[0].cluster.shard_nodes("ce", s)[0].id
                  for s in range(n_shards)}
        assert len(owners) == 2, "ModHasher should use both nodes"
        assert {c["node"] for c in children} == owners
        assert sum(c["shards"] for c in children) == n_shards
        for c in children:
            assert c["plan"]["op"] == "Count"
            assert c["plan"]["strategy"] in ("stacked", "per-shard")

        # explain=analyze: every leg executed its own analyze; the
        # merged result is correct and each sub-plan carries actuals
        resp = h[0].client.query("ce", "Count(Row(f=1))",
                                 explain="analyze")
        assert resp["results"] == [len(cols)]
        top = resp["plan"]["calls"][0]
        assert top["strategy"] == "cluster-map-reduce"
        assert {c["node"] for c in top["children"]} == owners
        for c in top["children"]:
            assert c["plan"]["actual"]["wall_seconds"] > 0
        assert "misestimates" in resp["plan"]
    finally:
        h.close()


# ------------------------------------------------------------ CLI flags


def test_cli_flags_fold_into_config():
    import io
    from contextlib import redirect_stdout

    from pilosa_tpu.cli import main

    import tomllib

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["config", "--plan-ring-size", "9",
                   "--explain-misestimate-factor", "1.5"])
    assert rc == 0
    cfg = tomllib.loads(buf.getvalue())
    assert cfg["plan-ring-size"] == 9
    assert cfg["explain-misestimate-factor"] == 1.5


def test_plan_configure_applies():
    old = plan_mod.stats()
    try:
        plan_mod.configure(ring_size=5, misestimate_factor=2.5)
        assert plan_mod.stats()["ring_size"] == 5
        assert plan_mod.misestimate_factor() == 2.5
    finally:
        plan_mod.configure(ring_size=old["ring_size"],
                           misestimate_factor=old["misestimate_factor"])

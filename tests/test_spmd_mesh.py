"""Mesh-resident SPMD serving over a REAL 2-process gloo CPU mesh.

The acceptance differential for --spmd-serve (ISSUE 18): with gloo
collectives the 2-process mesh actually forms on single-chip CI hosts
(unlike tests/test_spmd.py's plane, which needs one real device per
process), so these tests assert the serving contract, not just probe it:

- on == off == http bit-exact over the PR-10/PR-16 query mix, cold and
  warm (mesh-cache hits and fused collective programs included);
- a warm fused multi-call query runs ONE collective step per process
  and moves ZERO result bytes over the HTTP data plane;
- step-stream lifecycle counters stay consistent (entered == exited,
  no stream errors) and ?explain reports the mesh plan.

Slow: boots two jax.distributed server subprocesses (~15s). Run via
`make test-spmd-mesh`; gated by the same env switch as the other
subprocess suites.
"""

import os
import time

import pytest

from pilosa_tpu.cluster.spmd import STEP_PHASES
from pilosa_tpu.shardwidth import SHARD_WIDTH

from .harness import SpmdMeshCluster

pytestmark = [
    pytest.mark.slow,
    pytest.mark.skipif(
        os.environ.get("PILOSA_TPU_PROC_TESTS", "1") == "0",
        reason="process cluster tests disabled"),
]

#: the differential mix: every collective kind, BSI conditions, a time
#: range, and one non-collective call that stays on HTTP either way
QUERY_MIX = [
    "Count(Row(f=1))",
    "Count(Intersect(Row(f=1), Row(g=2)))",
    "Count(Union(Row(f=1), Row(g=2)))",
    "Count(Difference(Row(f=1), Row(g=2)))",
    "Count(Row(v > 0))",
    "Count(Row(v >< [-10, 10]))",
    "Sum(field=v)",
    "Sum(Row(f=1), field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "TopN(f, n=2)",
    "GroupBy(Rows(f), Rows(g))",
    "Count(Row(t=1, from=2019-01-01T00:00, to=2019-02-01T00:00))",
    "Row(f=1)",
]


@pytest.fixture(scope="module")
def cluster():
    c = SpmdMeshCluster(2)
    try:
        c.wait_ready()
        coord = c.clients[c.coord]
        coord.create_index("m")
        coord.create_field("m", "f")
        coord.create_field("m", "g")
        coord.create_field("m", "v", options={"type": "int",
                                              "min": -1000, "max": 1000})
        coord.create_field("m", "t", options={"type": "time",
                                              "timeQuantum": "YMD"})
        time.sleep(1.0)  # DDL broadcast settles
        # 4 shards -> 2 per process; mixed densities so the PR-10
        # chooser's repr verdicts differ per fragment
        cols = [s * SHARD_WIDTH + off for s in range(4)
                for off in (0, 7, 99, 1000)]
        coord.import_bits("m", "f", [1] * len(cols), cols)
        coord.import_bits("m", "g", [2] * (len(cols) // 2), cols[::2])
        vals = [((i * 37) % 2001) - 1000 for i in range(len(cols))]
        coord.import_values("m", "v", cols, vals)
        coord.import_bits("m", "t", [1] * 4,
                          [s * SHARD_WIDTH + 13 for s in range(4)],
                          timestamps=["2019-01-02T03:04"] * 2
                          + ["2020-06-07T08:09"] * 2)
        c.expect = {"cols": cols, "vals": vals}
        yield c
    finally:
        c.close()


def _run_mix(coord):
    return [coord.query("m", q)["results"] for q in QUERY_MIX]


def test_on_matches_off_and_http_bit_exact(cluster):
    """THE acceptance differential: the mesh-resident plane (cold AND
    warm — second pass hits the mesh cache and fused programs), the
    legacy blocking step plane, and the plain HTTP fan-out all return
    identical results for the full query mix."""
    coord = cluster.clients[cluster.coord]
    cluster.set_mode("on")
    on_cold = _run_mix(coord)
    on_warm = _run_mix(coord)
    cluster.set_mode("off")
    legacy = _run_mix(coord)
    cluster.set_mode("http")
    http = _run_mix(coord)
    cluster.set_mode("on")
    for q, a, b, c, d in zip(QUERY_MIX, on_cold, on_warm, legacy, http):
        assert a == b == c == d, (q, a, b, c, d)
    # sanity against ground truth, not just cross-plane agreement
    cols, vals = cluster.expect["cols"], cluster.expect["vals"]
    assert on_cold[0] == [len(cols)]
    assert on_cold[4] == [sum(1 for v in vals if v > 0)]
    assert on_cold[6] == [{"value": sum(vals), "count": len(vals)}]


def test_warm_fused_query_one_dispatch_zero_http_bytes(cluster):
    """A warm multi-call cluster query = ONE fused collective step per
    process and ZERO result bytes over the HTTP data plane."""
    coord = cluster.clients[cluster.coord]
    cluster.set_mode("on")
    pql = ("Count(Row(f=1)) Count(Row(g=2)) "
           "Count(Intersect(Row(f=1), Row(g=2)))")
    cols = cluster.expect["cols"]
    want = [len(cols), len(cols[::2]), len(cols[::2])]
    # cold runs accumulate fingerprint hits past the fusion min-hits
    # floor (2); the fused path must admit by the 3rd run
    for _ in range(3):
        assert coord.query("m", pql)["results"] == want
    before = [cluster.debug(i) for i in range(2)]
    assert coord.query("m", pql)["results"] == want
    after = [cluster.debug(i) for i in range(2)]
    for b, a in zip(before, after):
        assert a["steps"]["run"] - b["steps"]["run"] == 1, (b, a)
        assert a["http_data_plane_bytes"] == b["http_data_plane_bytes"]
    co, cb = after[cluster.coord], before[cluster.coord]
    assert co["queries"]["fused"] - cb["queries"]["fused"] == 1
    assert co["steps"]["fused"] - cb["steps"]["fused"] == 1
    # the fused collective program is in the fusion ledger, mesh-tagged
    fusion = coord._request("GET", "/debug/fusion")
    mesh_programs = [p for p in fusion["programs"] if p.get("mesh")]
    assert mesh_programs and mesh_programs[0]["mesh"] == [2, 2]


def _find_spmd_nodes(node, out):
    if isinstance(node, dict):
        ann = node.get("annotations") or {}
        if ann.get("spmd"):
            out.append(node)
        # per-node fan-out children wrap their sub-plan in {"plan": ...}
        if isinstance(node.get("plan"), dict):
            _find_spmd_nodes(node["plan"], out)
        for child in node.get("children") or []:
            _find_spmd_nodes(child, out)
    return out


def test_explain_reports_mesh_plan(cluster):
    coord = cluster.clients[cluster.coord]
    cluster.set_mode("on")
    # ?explain=true: annotated, nothing executes (no step advances)
    before = cluster.stats(cluster.coord)["steps"]
    resp = coord.query("m", "Count(Row(f=1))", explain="true")
    assert resp["results"] == []
    assert cluster.stats(cluster.coord)["steps"] == before
    nodes = _find_spmd_nodes({"children": resp["plan"]["calls"]}, [])
    assert nodes, resp["plan"]
    assert any(n.get("strategy") == "spmd-collective" for n in nodes)
    assert any(n["annotations"].get("dispatches") == 0 for n in nodes)
    assert any(n["annotations"].get("mesh") == [2, 2] for n in nodes)

    # ?explain=analyze: really executes over the mesh and grafts the
    # single dispatch + psum bytes (PR-16 fused-analyze contract)
    resp = coord.query("m", "Count(Row(f=1))", explain="analyze")
    assert resp["results"] == [len(cluster.expect["cols"])]
    nodes = _find_spmd_nodes({"children": resp["plan"]["calls"]}, [])
    analyzed = [n for n in nodes
                if n["annotations"].get("dispatches") == 1]
    assert analyzed, nodes
    assert analyzed[0]["annotations"]["psum_bytes"] >= 8


def test_stream_lifecycle_counters_consistent(cluster):
    """After everything above: every announced step entered and exited
    on both processes, the stream saw no errors or resyncs, and the
    wedge classifier would read this node as healthy."""
    cluster.set_mode("on")
    for i in range(2):
        d = cluster.debug(i)
        assert d["enabled"] and d["serve_mode"] == "on"
        assert d["mesh"] == [2, 2]
        s = d["steps"]
        assert s["entered"] == s["exited"] > 0, s
        assert d["stream"]["errors"] == 0
        assert d["stream"]["resyncs"] == 0
    coord = cluster.debug(cluster.coord)
    assert coord["steps"]["announced"] > 0
    assert coord["steps"]["last_seq"] > 0


def test_merged_timeline_both_peers_phase_sums_no_false_stragglers(cluster):
    """PR-19 acceptance on the live mesh: GET /debug/spmd/steps returns
    a skew-corrected per-peer timeline where BOTH processes report every
    step, each peer's phases sum to its step wall (≤5% residual), and a
    warm same-host mesh flags zero stragglers (the 25ms noise floor
    swallows scheduler jitter)."""
    coord = cluster.clients[cluster.coord]
    cluster.set_mode("on")
    # warm the collective kinds first so no one-sided compile wall lands
    # in the sampled steps and masquerades as a straggler
    warm = ("Count(Row(f=1))", "Sum(field=v)", "TopN(f, n=2)")
    for q in warm:
        coord.query("m", q)
    # the steps these three queries make are the newest that many of
    # the timeline: `last_seq` cannot mark them, because a blocking
    # step's seq (the step id) and a streamed step's (the stream's own
    # counter) are two counters, and this cluster has run in both modes
    ran = cluster.debug(cluster.coord)["steps"]["run"]
    for q in warm:
        coord.query("m", q)
    ran = cluster.debug(cluster.coord)["steps"]["run"] - ran

    tl = coord._request("GET", "/debug/spmd/steps?limit=64")
    assert tl["enabled"] is True
    assert len(tl["skew_seconds"]) == 2  # one envelope theta per node
    assert ran >= len(warm), tl["steps"]
    fresh = sorted(tl["steps"], key=lambda s: min(
        p["start"] for p in s["peers"].values()))[-ran:]
    for s in fresh:
        assert len(s["peers"]) == 2, s
        for peer in s["peers"].values():
            wall = peer["wall_seconds"]
            assert set(peer["phases"]) <= set(STEP_PHASES)
            residual = abs(sum(peer["phases"].values()) - wall)
            assert residual <= 0.05 * wall + 1e-5, (residual, peer)
        # same-host processes: skew-corrected starts must line up far
        # tighter than uncorrected wall clocks ever need to
        starts = [p["start"] for p in s["peers"].values()]
        assert max(starts) - min(starts) < 1.0, s
        assert s["stragglers"] == [], s

    # the single-seq endpoint returns exactly that step, both peers
    seq = fresh[-1]["seq"]
    one = coord._request("GET", "/debug/spmd/steps/%d" % seq)
    assert [x["seq"] for x in one["steps"]] == [seq]
    assert len(one["steps"][0]["peers"]) == 2

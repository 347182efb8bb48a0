"""Adaptive execution engine (exec/adaptive.py).

The load-bearing contract is that adaptivity NEVER changes answers:
`--adaptive on` must be bit-identical to `off` across the differential
corpus (stacked counts, per-shard fallbacks, pairwise GroupBy,
compressed containers), and `shadow` must additionally
leave every side-effect surface untouched (cache pools evict LRU, no
repr overrides land) while still pricing and logging every decision.

Alongside: the benefit-score eviction oracles (hot entries survive a
constrained budget where LRU would strip them), the calibration ladder
(ewma > cost_analysis > default), proactive admission converging
/debug/heat's hot_but_not_resident list, misestimate feedback, the
kernel_seconds EWMA satellite in utils/stats.py, and the dispatch-free
EXPLAIN contract for `chosen_by`.
"""

import json

import numpy as np
import pytest

from pilosa_tpu.core import FieldOptions, Holder
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec import ExecOptions, Executor
from pilosa_tpu.exec import adaptive
from pilosa_tpu.exec import plan as plan_mod
from pilosa_tpu.exec import stacked as stacked_mod
from pilosa_tpu.ops import containers as cont
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import workload
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture(autouse=True)
def _isolate():
    """Adaptive state is module-singleton (like exec/plan.py): reset the
    engine, the heat ledger, and any container repr overrides around
    every test, and restore the stack-cache budget tests shrink."""
    prev_budget = stacked_mod.MAX_STACK_BYTES
    prev_mode, prev_floor = cont.repr_mode(), cont.AUTO_COMPRESS_FLOOR
    adaptive.reset()
    workload.reset()
    yield
    stacked_mod.MAX_STACK_BYTES = prev_budget
    cont.configure(prev_mode)
    cont.AUTO_COMPRESS_FLOOR = prev_floor
    cont.reset_ledger()
    adaptive.reset()
    workload.reset()


# ------------------------------------------------------------ unit oracles


def test_modes_and_reset():
    assert adaptive.mode() == "off"
    assert not adaptive.enabled() and not adaptive.acting()
    adaptive.configure(mode="shadow")
    assert adaptive.enabled() and not adaptive.acting()
    adaptive.configure(mode="on")
    assert adaptive.enabled() and adaptive.acting()
    with pytest.raises(ValueError):
        adaptive.configure(mode="sometimes")
    adaptive.reset()
    assert adaptive.mode() == "off"


def test_off_mode_is_inert():
    """Mode off: no decisions, no learning — the legacy-path guarantee
    reduces to these early returns plus the callers' enabled() gates."""
    assert adaptive.decide_strategy("Count", {"count": 1}, 4) is None
    assert adaptive.decide_tile(64, 10, 10) is None
    adaptive.observe_fallback("Count", 0.5, 4)
    adaptive.observe_pairwise(64, 0.01)
    adaptive.note_wall_misestimate({"count": 2}, 0.5)
    adaptive.note_repr_misestimate("i", ["f"])
    snap = adaptive.snapshot()
    assert snap["calibration"]["fallback"] == {}
    assert snap["calibration"]["pairwise_tiles"] == {}
    assert snap["recent"] == []
    assert snap["calibration_bumps"] == {}


def test_benefit_score_shape():
    # more heat -> better keep (higher score)
    assert adaptive.benefit_score(5.0, 1024) > adaptive.benefit_score(
        1.0, 1024)
    # same heat, more resident bytes -> worse keep (fixed rebuild cost
    # amortizes over more HBM)
    assert adaptive.benefit_score(1.0, 1 << 20) < adaptive.benefit_score(
        1.0, 1 << 10)
    # zero heat scores zero regardless of size
    assert adaptive.benefit_score(0.0, 1 << 30) == 0.0


def test_select_victim_prefers_cold_and_large():
    # cold entry loses to hot entry at equal size
    assert adaptive.select_victim(
        [("hot", 5.0, 1024), ("cold", 0.1, 1024)]) == "cold"
    # equal heat: the larger entry is the better victim
    assert adaptive.select_victim(
        [("small", 1.0, 1024), ("big", 1.0, 1 << 24)]) == "big"
    # exact ties fall back to FIFO position = LRU behavior
    assert adaptive.select_victim(
        [("lru", 1.0, 1024), ("mru", 1.0, 1024)]) == "lru"


def test_decide_strategy_default_calibration():
    # synthetic kernel family: real ones ("count") may carry EWMA
    # samples in the process-global stats registry from earlier tests
    adaptive.configure(mode="on")
    # 1 dispatch vs 4 shards at equal per-unit defaults: stacked wins
    dec = adaptive.decide_strategy("Count", {"_unit_probe": 1}, 4)
    assert dec.strategy == "stacked" and dec.act
    assert dec.source == "default"
    assert "cost-model" in dec.chosen_by
    assert "ms" in dec.chosen_by
    # a mountain of cold upload bytes flips the same shape to fallback
    dec = adaptive.decide_strategy("Count", {"_unit_probe": 1}, 4,
                                   missing_bytes=1 << 34)
    assert dec.strategy == "fallback"
    assert dec.est_stacked > dec.est_fallback


def test_decide_strategy_learns_from_fallback_walls():
    adaptive.configure(mode="on")
    # teach a very cheap per-shard fallback: 2 shards at ~1us beats the
    # 2ms default dispatch price
    for _ in range(3):
        adaptive.observe_fallback("Count", 2e-6, 2)
    dec = adaptive.decide_strategy("Count", {"_unit_probe": 1}, 2)
    assert dec.strategy == "fallback"
    assert dec.source == "default"  # worst input still the kernel default
    snap = adaptive.snapshot()
    assert snap["calibration"]["fallback"]["Count"]["samples"] == 3


def test_decide_strategy_shadow_never_acts():
    adaptive.configure(mode="shadow")
    dec = adaptive.decide_strategy("Count", {"_unit_probe": 1}, 4)
    assert dec is not None and not dec.act
    # shadow still learns and still counts
    adaptive.observe_fallback("Count", 0.5, 4)
    snap = adaptive.snapshot()
    assert snap["decisions"]["strategy"]["Count"]["stacked"] == 1
    assert snap["calibration"]["fallback"]["Count"]["samples"] == 1


def test_decide_tile_static_without_samples():
    """No pairwise observations: every candidate prices at the same
    per-dispatch overhead, the dispatch-count term dominates, and the
    static (largest) tile must win — the legacy choice."""
    adaptive.configure(mode="on")
    dec = adaptive.decide_tile(64, 100, 100)
    assert dec.tile == 64 and dec.act
    assert dec.source == "default"
    assert set(dec.estimates) == {64, 32, 16, 8}


def test_decide_tile_shrinks_when_cells_dominate():
    """Feed walls where the t² term dwarfs overhead, on a row set much
    smaller than the static tile: the padded static dispatch pays the
    full t² cells for mostly-padding rows, so a smaller covering tile
    must win."""
    adaptive.configure(mode="on")
    adaptive.observe_pairwise(8, 1e-4)      # near-pure overhead probe
    adaptive.observe_pairwise(64, 0.4)      # cell term >> overhead
    dec = adaptive.decide_tile(64, 10, 10)
    assert dec.tile < 64
    assert dec.tile >= 10  # still covers each axis in one dispatch
    assert dec.source == "ewma"
    assert dec.estimates[dec.tile] <= dec.estimates[64]


def test_decide_tile_forced_override():
    adaptive.configure(mode="on")
    adaptive.set_forced_tile(16)
    dec = adaptive.decide_tile(64, 100, 100)
    assert dec.tile == 16
    adaptive.set_forced_tile(None)
    dec = adaptive.decide_tile(64, 100, 100)
    assert dec.tile == 64


def test_stats_timing_ewma_satellite():
    """utils/stats.py satellite: the kernel_seconds series gains a
    recency-weighted EWMA view while the cumulative /metrics fields
    (count, sum, buckets) stay untouched."""
    tags = {"kernel": "_ewma_probe"}
    global_stats.timing("kernel_seconds", 0.010, tags)
    global_stats.timing("kernel_seconds", 0.020, tags)
    ew = {dict(k[1]).get("kernel"): v
          for k, v in global_stats.timing_ewma("kernel_seconds").items()}
    ewma, n = ew["_ewma_probe"]
    assert n == 2
    # first sample seeds, second moves by alpha
    assert ewma == pytest.approx(0.010 + 0.2 * (0.020 - 0.010))
    # force overwrites only the EWMA field, not count/sum
    global_stats.timing_ewma_force("kernel_seconds", 0.5, tags)
    ew = {dict(k[1]).get("kernel"): v
          for k, v in global_stats.timing_ewma("kernel_seconds").items()}
    assert ew["_ewma_probe"] == (0.5, 2)


def test_wall_misestimate_reseeds_calibration():
    adaptive.configure(mode="on")
    tags = {"kernel": "_mis_probe"}
    global_stats.timing("kernel_seconds", 1e-4, tags)
    # observed wall 10x the estimate: 2 dispatches took 0.2s
    adaptive.note_wall_misestimate({"_mis_probe": 2}, 0.2)
    secs, src = adaptive.dispatch_seconds("_mis_probe")
    assert src == "ewma"
    assert secs == pytest.approx(0.1)
    assert adaptive.snapshot()["calibration_bumps"]["_mis_probe"] == 1


def test_repr_misestimate_strikes_force_dense():
    adaptive.configure(mode="shadow")
    # shadow: strikes accumulate, no override lands
    adaptive.note_repr_misestimate("i", ["f"])
    adaptive.note_repr_misestimate("i", ["f"])
    assert cont.repr_override("i", "f") is None
    assert adaptive.snapshot()["repr_strikes"]["i/f"] == 2
    adaptive.reset()
    adaptive.configure(mode="on")
    adaptive.note_repr_misestimate("i", ["f"])
    assert cont.repr_override("i", "f") is None  # one strike: not yet
    adaptive.note_repr_misestimate("i", ["f"])
    assert cont.repr_override("i", "f") == "dense"
    cont.reset_ledger()
    assert cont.repr_override("i", "f") is None


# ------------------------------------------------------ differential corpus


def _populate(h):
    """Multi-shard corpus covering every adaptive decision point: set
    fields for Count/TopN/GroupBy (2-3 shards, above MIN_SHARDS), a BSI
    int field for Sum/Min/Max, and a single-shard field whose queries
    stay on the per-shard fallback."""
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(7)
    rows, cols = [], []
    for row in range(6):
        for shard in range(3):
            n = int(rng.integers(1, 40))
            c = rng.choice(SHARD_WIDTH, size=n, replace=False)
            rows.extend([row] * n)
            cols.extend((shard * SHARD_WIDTH + c).tolist())
    f.import_bits(np.asarray(rows, dtype=np.uint64),
                  np.asarray(cols, dtype=np.uint64))
    g = idx.create_field("g")
    g.import_bits(
        np.asarray([10] * 3 + [11] * 3, dtype=np.uint64),
        np.asarray([0, 5, SHARD_WIDTH + 1, 7, SHARD_WIDTH + 9,
                    2 * SHARD_WIDTH + 3], dtype=np.uint64))
    idx.create_field("n", FieldOptions.int_field(min=-1000, max=1000))
    e = Executor(h)
    e.execute("i", "Set(1, n=100) Set(2, n=-300) Set(3, n=42)"
                   f" Set({SHARD_WIDTH + 4}, n=7)"
                   f" Set({2 * SHARD_WIDTH + 8}, n=-9)")
    # single-shard field: stays under MIN_SHARDS, exercises the
    # fallback path alongside the stacked one
    s = idx.create_field("s")
    s.import_bits(np.asarray([1, 1, 2], dtype=np.uint64),
                  np.asarray([0, 3, 4], dtype=np.uint64))
    return idx


QUERIES = (
    "Count(Row(f=0))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Count(Union(Row(f=0), Row(f=3), Row(f=5)))",
    "Count(Row(s=1))",                       # single shard: fallback
    "Row(f=4)",
    "Sum(field=n)",
    "Sum(Row(f=1), field=n)",
    "Min(field=n)",
    "Max(field=n)",
    "TopN(f, n=4)",
    "TopN(f, Row(g=10), n=3)",
    "GroupBy(Rows(f, limit=3), Rows(g))",    # pairwise tiles
    "GroupBy(Rows(g))",                      # single-field row_counts
)

#: count shapes of one signature, run once more after the corpus
BATCH = ["Count(Row(f=%d))" % r for r in range(4)]


def _normalize(res):
    out = []
    for r in res:
        columns = getattr(r, "columns", None)
        out.append(tuple(columns()) if callable(columns) else r)
    return out


def _run_corpus(holder, repeat=2):
    """Fresh executor, the full corpus `repeat` times (cold build then
    warm cache — the adaptive engine sees both regimes), plus one
    round of same-signature Counts. Returns (executor, results)."""
    ex = Executor(holder)
    out = []
    for _ in range(repeat):
        for q in QUERIES:
            out.append(_normalize(ex.execute("i", q)))
    for q in BATCH:
        out.append(_normalize(ex.execute("i", q)))
    return ex, out


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    h = Holder(str(tmp_path_factory.mktemp("adaptive")),
               use_snapshot_queue=False).open()
    _populate(h)
    yield h
    h.close()


def _baseline(corpus):
    """Mode-off reference answers, under the same container config the
    adaptive run will use (the compressed-container dimension is covered
    WITH adaptivity, not confounded by it)."""
    adaptive.reset()  # mode off
    cont.AUTO_COMPRESS_FLOOR = 0
    cont.configure("auto")
    _, want = _run_corpus(corpus)
    return want


def test_adaptive_on_bit_identical(corpus):
    """The acceptance gate: --adaptive on answers exactly like off over
    stacked, fallback, pairwise GroupBy and compressed containers."""
    want = _baseline(corpus)
    adaptive.configure(mode="on")
    ex, got = _run_corpus(corpus)
    assert got == want
    # the engine actually decided things along the way
    snap = adaptive.snapshot(stacked=ex._stacked)
    assert sum(n for per_op in snap["decisions"]["strategy"].values()
               for n in per_op.values()) > 0


def test_shadow_zero_side_effects(corpus):
    """Shadow prices and logs every decision but acts on none: answers,
    cache-pool contents, and repr overrides all match mode off."""
    want = _baseline(corpus)
    ex_off, _ = _run_corpus(corpus)
    off_pools = (sorted(map(str, ex_off._stacked._stacks)),
                 sorted(map(str, ex_off._stacked._rows_stacks)))

    adaptive.configure(mode="shadow")
    ex, got = _run_corpus(corpus)
    assert got == want
    pools = (sorted(map(str, ex._stacked._stacks)),
             sorted(map(str, ex._stacked._rows_stacks)))
    assert pools == off_pools
    assert cont.repr_overrides() == {}
    snap = adaptive.snapshot(stacked=ex._stacked)
    assert snap["mode"] == "shadow"
    assert snap["recent"]  # decisions were priced and logged...
    assert snap["decisions"]["cache"]["benefit_evictions"] == 0  # not acted


def test_explain_chosen_by_dispatch_free(corpus):
    """EXPLAIN surfaces chosen_by + both priced alternatives from the
    plan path with ZERO dispatches (the /debug/plans contract)."""
    adaptive.configure(mode="on")
    ex = Executor(corpus)
    before = ex._stacked.dispatches

    def walk(node):
        yield node
        for c in node.get("children", ()):
            yield from walk(c)

    anns = []
    for q in ("Count(Row(f=0))", "Sum(field=n)",
              "GroupBy(Rows(f, limit=3), Rows(g))"):
        assert ex.execute("i", q,
                          options=ExecOptions(explain="plan")) == []
        plan = plan_mod.take_last()
        assert plan is not None, q
        anns.extend(n["annotations"] for call in plan["calls"]
                    for n in walk(call)
                    if "chosen_by" in n.get("annotations", {}))
    assert ex._stacked.dispatches == before
    assert anns, "no chosen_by annotation on any plan node"
    for ann in anns:
        assert "cost-model" in ann["chosen_by"]
        alt = ann["alternatives"]
        assert set(alt) >= {"stacked_ms", "fallback_ms", "cost_source"}
        assert alt["cost_source"] in ("ewma", "cost_analysis", "default")


def test_debug_optimizer_snapshot_shape(corpus):
    adaptive.configure(mode="on")
    ex, _ = _run_corpus(corpus, repeat=1)
    snap = adaptive.snapshot(stacked=ex._stacked)
    assert snap["mode"] == "on"
    assert set(snap["calibration"]) == {
        "kernels", "fallback", "pairwise_tiles",
        "default_dispatch_seconds"}
    for fam, entry in snap["calibration"]["kernels"].items():
        assert entry["source"] in ("ewma", "cost_analysis", "default")
    assert set(snap["decisions"]) == {
        "strategy", "tile", "cache", "admission", "patch"}
    json.dumps(snap)  # the /debug/optimizer endpoint serves this as-is
    counts = adaptive.decision_counts()
    assert set(counts) == {"strategy", "tile", "cache", "admission",
                           "patch"}
    json.dumps(counts)


# ------------------------------------------------- cache policy integration


def test_benefit_eviction_keeps_hot_entry(tmp_path):
    """Constrained budget, one hot field: LRU (off) evicts the oldest =
    hottest entry; the benefit policy (on) keeps it and sheds a cold
    one instead."""
    h = Holder(str(tmp_path / "d"), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        for name in ("hot", "cold", "late"):
            fld = idx.create_field(name)
            fld.import_bits(
                np.asarray([1, 1], dtype=np.uint64),
                np.asarray([0, SHARD_WIDTH + 1], dtype=np.uint64))
        adaptive.configure(mode="on")
        # pin the strategy side: an expensive taught fallback keeps all
        # three Counts on the stacked path (kernel EWMAs in the global
        # stats registry would otherwise make CPU compile walls flip
        # them to fallback and build no stacks at all)
        adaptive.observe_fallback("Count", 100.0, 1)
        ex = Executor(h)
        ex.execute("i", "Count(Row(hot=1))")   # oldest entry = LRU victim
        ex.execute("i", "Count(Row(cold=1))")
        pool = ex._stacked._stacks
        assert len(pool) == 2
        # demand makes it hot (far above the single build-probe bumps)
        for _ in range(50):
            workload.heat_bump("i", "hot", VIEW_STANDARD)
        # budget admits exactly what's resident: the next insert evicts
        stacked_mod.MAX_STACK_BYTES = ex._stacked._stack_bytes
        ex.execute("i", "Count(Row(late=1))")
        fields = sorted(k[2] for k in pool)
        assert "hot" in fields, f"benefit policy evicted the hot entry: {fields}"
        assert "cold" not in fields
        snap = adaptive.snapshot()
        assert snap["decisions"]["cache"]["benefit_evictions"] >= 1
        assert snap["decisions"]["cache"]["lru_evictions"] == 0
    finally:
        h.close()


def test_shadow_eviction_is_lru_but_counts_divergence(tmp_path):
    h = Holder(str(tmp_path / "d"), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        for name in ("hot", "cold", "late"):
            fld = idx.create_field(name)
            fld.import_bits(
                np.asarray([1, 1], dtype=np.uint64),
                np.asarray([0, SHARD_WIDTH + 1], dtype=np.uint64))
        adaptive.configure(mode="shadow")
        adaptive.observe_fallback("Count", 100.0, 1)  # see test above
        ex = Executor(h)
        ex.execute("i", "Count(Row(hot=1))")
        ex.execute("i", "Count(Row(cold=1))")
        for _ in range(50):
            workload.heat_bump("i", "hot", VIEW_STANDARD)
        stacked_mod.MAX_STACK_BYTES = ex._stacked._stack_bytes
        ex.execute("i", "Count(Row(late=1))")
        # LRU still ruled: the hot (oldest) entry went
        fields = sorted(k[2] for k in ex._stacked._stacks)
        assert "hot" not in fields
        snap = adaptive.snapshot()
        assert snap["decisions"]["cache"]["lru_evictions"] >= 1
        assert snap["decisions"]["cache"]["benefit_evictions"] == 0
        assert snap["decisions"]["cache"]["shadow_divergences"] >= 1
    finally:
        h.close()


# ------------------------------------------------------ proactive admission


def test_proactive_admission_converges_heat(tmp_path):
    """Demand heat without residency -> maybe_proactive_admit builds the
    stack in the idle window, the heat ledger converges (the fragment
    leaves hot_but_not_resident), and the admission counter moves."""
    h = Holder(str(tmp_path / "d"), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        fld = idx.create_field("f")
        fld.import_bits(
            np.asarray([1, 1, 2], dtype=np.uint64),
            np.asarray([0, SHARD_WIDTH + 1, 5], dtype=np.uint64))
        adaptive.configure(mode="on")
        ex = Executor(h)
        # hot demand that never built a stack
        for _ in range(10):
            workload.heat_bump("i", "f", VIEW_STANDARD)
        report = workload.heat().report(ex._stacked.hbm_snapshot(top=0))
        assert any(c["field"] == "f"
                   for c in report["hot_but_not_resident"])
        before = adaptive.decision_counts()["admission"]
        admitted = ex.maybe_proactive_admit()
        assert admitted >= 1
        after = adaptive.decision_counts()["admission"]
        assert after["admitted_fragments"] > before["admitted_fragments"]
        assert after["admitted_rows"] > 0 and after["admitted_bytes"] > 0
        # converged: resident now, and heat scaled down to the threshold
        report = workload.heat().report(ex._stacked.hbm_snapshot(top=0))
        assert not any(c["field"] == "f"
                       for c in report["hot_but_not_resident"])
        assert sum(workload.heat().value("i", "f", v)
                   for v in (VIEW_STANDARD,)) == pytest.approx(
                       workload.HEAT_HOT_MIN, rel=1e-3)
        # the admitted stack answers queries without another build
        misses = ex._stacked.misses
        assert _normalize(ex.execute("i", "Row(f=1)"))[0] == (
            0, SHARD_WIDTH + 1)
        assert ex._stacked.misses == misses
    finally:
        h.close()


def test_proactive_admission_shadow_counts_only(tmp_path):
    h = Holder(str(tmp_path / "d"), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        fld = idx.create_field("f")
        fld.import_bits(
            np.asarray([1, 1], dtype=np.uint64),
            np.asarray([0, SHARD_WIDTH + 1], dtype=np.uint64))
        adaptive.configure(mode="shadow")
        ex = Executor(h)
        for _ in range(10):
            workload.heat_bump("i", "f", VIEW_STANDARD)
        assert ex.maybe_proactive_admit() == 0
        counts = adaptive.decision_counts()["admission"]
        assert counts["shadow_candidates"] >= 1
        assert counts["admitted_fragments"] == 0
        assert len(ex._stacked._stacks) == 0  # nothing built
    finally:
        h.close()


def test_proactive_admission_off_is_noop(tmp_path):
    h = Holder(str(tmp_path / "d"), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        fld = idx.create_field("f")
        fld.import_bits(np.asarray([1], dtype=np.uint64),
                        np.asarray([0], dtype=np.uint64))
        ex = Executor(h)
        for _ in range(10):
            workload.heat_bump("i", "f", VIEW_STANDARD)
        assert ex.maybe_proactive_admit() == 0
        assert adaptive.decision_counts()["admission"]["rounds"] == 0
    finally:
        h.close()

"""Whole-plan fusion (exec/fusion.py): one jitted device program per
eligible query, cached by workload fingerprint.

The acceptance contract (ISSUE 16): `--fusion on` is bit-identical to
`off` across the differential corpus (multi-op chains, compressed
containers, 1..3-call batches); a warm fingerprint serves an N-call
query in exactly ONE device dispatch; a COLD fingerprint never pays a
compile; `shadow` counts would-fuse admissions with zero cache/compile
side effects; evicting a program also drops the jitted fn from the
evaluator cache; fused dispatches register with the watchdog/phase
clock like every other kernel family; and /debug/fusion serves the
program ledger over HTTP.
"""

import json
import re

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.exec import ExecOptions, Executor
from pilosa_tpu.exec import adaptive
from pilosa_tpu.exec import fusion
from pilosa_tpu.exec import plan as plan_mod
from pilosa_tpu.ops import containers as cont
from pilosa_tpu.pql import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import profile as profile_mod
from pilosa_tpu.utils import workload
from pilosa_tpu.utils.logger import CaptureLogger
from tests.harness import ServerHarness


@pytest.fixture(autouse=True)
def _isolate():
    """Fusion state is module-singleton (like exec/adaptive.py): reset
    the program ledger, the adaptive engine it consults, and the
    workload table that drives compile admission around every test."""
    prev_mode, prev_floor = cont.repr_mode(), cont.AUTO_COMPRESS_FLOOR
    fusion.reset()
    adaptive.reset()
    workload.reset()
    plan_mod.clear_recent()
    yield
    cont.configure(prev_mode)
    cont.AUTO_COMPRESS_FLOOR = prev_floor
    fusion.reset()
    adaptive.reset()
    workload.reset()
    plan_mod.clear_recent()


# ------------------------------------------------------------ unit oracles


def test_modes_and_reset():
    assert fusion.mode() == "off"
    assert not fusion.enabled() and not fusion.acting()
    fusion.configure(mode="shadow")
    assert fusion.enabled() and not fusion.acting()
    fusion.configure(mode="on")
    assert fusion.enabled() and fusion.acting()
    with pytest.raises(ValueError):
        fusion.configure(mode="sometimes")
    fusion.reset()
    assert fusion.mode() == "off"
    assert fusion.min_hits() == fusion.DEFAULT_MIN_HITS


def test_configure_clamps_knobs():
    fusion.configure(cache_size=0)       # floor: a 0-slot cache is off,
    snap = fusion.snapshot()             # and off already exists as a mode
    assert snap["cache_size"] == 1
    fusion.configure(min_hits=-5)
    assert fusion.min_hits() == 0


def test_off_mode_is_inert():
    """Mode off: the executor hook is maybe_execute's first return —
    no executor attribute is ever touched, so None stands in for one."""
    assert fusion.maybe_execute(None, None, None, None, None) is None
    assert fusion.last_fused() == 0
    snap = fusion.snapshot()
    assert snap["mode"] == "off"
    assert snap["entries"] == 0 and snap["programs"] == []
    assert all(v == 0 for v in fusion.decision_counts().values())


def test_note_fused_take_last():
    fusion.note_fused(3)
    assert fusion.last_fused() == 3
    fusion.note_fused(0)  # the executor's per-query reset
    assert fusion.last_fused() == 0


def test_decide_fuse_pricing():
    """Adaptive fuse-vs-interpret oracles: a cached program strictly
    dominates; a cold compile on a rare shape loses to interpreting a
    single call; frequency amortizes the compile away."""
    assert adaptive.decide_fuse(2, 5, True) is None  # engine off

    adaptive.configure(mode="on")
    dec = adaptive.decide_fuse(1, 1, True)
    assert dec.fuse and dec.act                      # sunk compile: fuse
    assert dec.est_fused <= dec.est_interpret
    # 1 call, seen once, no program: compile/1 >> one dispatch saved
    dec = adaptive.decide_fuse(1, 1, False)
    assert not dec.fuse
    # same shape seen 10k times, 4 calls: amortized compile vanishes
    dec = adaptive.decide_fuse(4, 10_000, False)
    assert dec.fuse
    assert "cost-model" in dec.chosen_by and "ms" in dec.chosen_by
    # decisions land in the shared strategy counters for /debug/optimizer
    counts = adaptive.decision_counts()["strategy"]
    assert sum(n for k, n in counts.items()
               if k.startswith("Fuse:")) == 3


def test_decide_fuse_shadow_does_not_act():
    adaptive.configure(mode="shadow")
    dec = adaptive.decide_fuse(1, 1, False)
    assert not dec.fuse and not dec.act  # priced, logged, never vetoes


def test_fingerprint_hits_is_not_an_access(tmp_path):
    """workload.fingerprint_hits reads the frequency count WITHOUT
    touching the entry (the admission gate must not inflate the signal
    it reads)."""
    h = Holder(str(tmp_path), use_snapshot_queue=False).open()
    try:
        idx = h.create_index("i")
        idx.create_field("f")
        ex = Executor(h)
        ex.execute("i", "Count(Row(f=1))")
        ex.execute("i", "Count(Row(f=2))")  # same shape, other literal
        fp, _ = workload.fingerprint("i", parse("Count(Row(f=3))"))
        assert workload.fingerprint_hits(fp) == 2
        for _ in range(50):  # probing must not count as traffic
            workload.fingerprint_hits(fp)
        assert workload.fingerprint_hits(fp) == 2
        assert workload.fingerprint_hits("0" * 16) == 0
    finally:
        h.close()


# ------------------------------------------------- differential corpus


def _populate(h):
    """Two set fields spread over 3 shards (>= MIN_SHARDS so the
    stacked/fused path engages) with deterministic contents."""
    idx = h.create_index("i")
    f = idx.create_field("f")
    rng = np.random.default_rng(16)
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
    return idx


#: 1..3-call batches over every coverable op — each multi-call query is
#: one fused program with one stacked (hi, lo) output
QUERIES = (
    "Count(Row(f=0))",
    "Count(Intersect(Row(f=1), Row(g=10)))",
    "Count(Union(Row(f=0), Row(f=3), Row(f=5)))",
    "Count(Difference(Row(f=1), Row(f=2)))",
    "Count(Xor(Row(f=2), Row(f=4)))",
    "Count(Row(f=0)) Count(Row(f=1))",
    "Count(Intersect(Row(f=1), Row(g=10))) Count(Row(f=2))"
    " Count(Union(Row(f=3), Row(f=4)))",
)


def _run_corpus(holder, repeat=2):
    ex = Executor(holder)
    out = []
    for _ in range(repeat):
        for q in QUERIES:
            out.append(ex.execute("i", q))
    return ex, out


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    h = Holder(str(tmp_path_factory.mktemp("fusion")),
               use_snapshot_queue=False).open()
    _populate(h)
    yield h
    h.close()


def test_fused_bit_identity(corpus):
    """The acceptance gate: --fusion on answers exactly like off over
    multi-op chains and 1..3-call batches, and actually fused."""
    fusion.reset()  # mode off baseline
    _, want = _run_corpus(corpus)

    fusion.configure(mode="on", min_hits=0)
    ex, got = _run_corpus(corpus)
    assert got == want
    dc = fusion.decision_counts()
    assert dc["fused"] > 0
    assert ex._stacked.cache_stats()["fused_dispatches"] > 0


def test_fused_bit_identity_compressed(corpus):
    """Same gate under forced container compression: sparse/RLE count
    programs inline into the fused trace (a distinct gsig key)."""
    fusion.reset()
    cont.AUTO_COMPRESS_FLOOR = 0
    cont.configure("auto")
    _, want = _run_corpus(corpus)

    fusion.configure(mode="on", min_hits=0)
    _, got = _run_corpus(corpus)
    assert got == want
    assert fusion.decision_counts()["fused"] > 0


def test_cold_fingerprint_never_compiles(corpus):
    """A shape below --fusion-min-hits runs interpreted with an empty
    program ledger; crossing the floor admits it."""
    fusion.configure(mode="on")  # default min_hits=2
    ex = Executor(corpus)
    q = "Count(Row(f=5)) Count(Row(g=11))"

    ex.execute("i", q)   # completed queries: 0 -> vetoed cold
    assert fusion.snapshot()["entries"] == 0
    assert fusion.decision_counts()["interpreted_cold"] == 1
    ex.execute("i", q)   # completed: 1 -> still cold
    assert fusion.snapshot()["entries"] == 0
    assert fusion.decision_counts()["interpreted_cold"] == 2

    ex.execute("i", q)   # completed: 2 >= floor -> traces
    snap = fusion.snapshot()
    assert snap["entries"] == 1
    assert snap["programs"][0]["compile_ms"] > 0
    assert fusion.decision_counts()["fused"] == 1


def test_single_dispatch_per_warm_query(corpus):
    """The headline claim: a warm 3-call query costs exactly ONE device
    dispatch (the legacy loop pays one per call)."""
    fusion.configure(mode="on", min_hits=0)
    ex = Executor(corpus)
    q = ("Count(Row(f=0)) Count(Intersect(Row(f=1), Row(g=10)))"
         " Count(Row(f=3))")
    ex.execute("i", q)  # compile round
    before = ex._stacked.dispatches
    ex.execute("i", q)
    assert ex._stacked.dispatches - before == 1
    assert fusion.last_fused() == 3


def test_program_shared_across_literals(corpus):
    """`Count(Row(f=3))` and `Count(Row(f=9))` are the same program:
    the cache key is the literal-free fingerprint + gsigs + bucket."""
    fusion.configure(mode="on", min_hits=0)
    ex = Executor(corpus)
    for row in (0, 1, 2, 3):
        ex.execute("i", f"Count(Row(f={row}))")
    snap = fusion.snapshot()
    assert snap["entries"] == 1
    assert snap["programs"][0]["hits"] == 4
    assert fusion.decision_counts()["fused"] == 4


def test_shadow_zero_side_effects(corpus):
    """Shadow admits and counts but compiles nothing: answers, program
    ledger, and the evaluator dispatch mix all match mode off."""
    fusion.reset()
    ex_off, want = _run_corpus(corpus)
    off_fused = ex_off._stacked.cache_stats()["fused_dispatches"]

    fusion.configure(mode="shadow", min_hits=0)
    ex, got = _run_corpus(corpus)
    assert got == want
    snap = fusion.snapshot()
    assert snap["mode"] == "shadow"
    assert snap["entries"] == 0
    dc = fusion.decision_counts()
    assert dc["shadow_would_fuse"] > 0
    assert dc["fused"] == 0
    assert ex._stacked.cache_stats()["fused_dispatches"] == off_fused == 0


def test_lru_eviction_drops_compiled_fn(corpus):
    """A 1-slot cache: warming a second shape evicts the first AND pops
    its jitted fn from the evaluator cache, so re-entry re-compiles."""
    fusion.configure(mode="on", min_hits=0, cache_size=1)
    ex = Executor(corpus)
    fused_keys = lambda: [k for k in ex._stacked._fns  # noqa: E731
                          if isinstance(k, tuple) and k and k[0] == "fused"]

    ex.execute("i", "Count(Row(f=0))")
    assert len(fused_keys()) == 1
    ex.execute("i", "Count(Row(f=1)) Count(Row(f=2))")  # distinct shape
    snap = fusion.snapshot()
    assert snap["entries"] == 1
    assert snap["evictions"] == 1
    assert snap["programs"][0]["calls"] == 2  # survivor is the 2-call shape
    assert len(fused_keys()) == 1  # evicted program's fn is GONE

    rec = fusion.decision_counts()
    assert rec["fused"] == 2


def test_watchdog_and_phase_clock_registration(corpus):
    """Fused dispatches go through _locked_dispatch like every kernel
    family: per-family attribution and the phase decomposition both
    carry a 'fused' entry."""
    fusion.configure(mode="on", min_hits=0)
    ex = Executor(corpus)
    ex.execute("i", "Count(Row(f=0)) Count(Row(f=1))")  # compile round
    ex.execute("i", "Count(Row(f=2)) Count(Row(f=3))")  # warm round
    fam = ex._stacked._kernels.get("fused")
    assert fam is not None and fam["count"] == 2
    assert fam["bytes_in"] > 0
    phases = ex._stacked.dispatch_phases().get("fused")
    assert phases is not None
    # first dispatch relabels ack as "compile"; the warm one acks
    assert {"compile", "dispatch_ack", "sync"} <= set(phases)


def test_groupby_stays_interpreted(corpus):
    """Non-Count top-level calls are ineligible — the whole query runs
    the legacy loop (bit-identical by construction)."""
    fusion.reset()
    ex = Executor(corpus)
    q = "GroupBy(Rows(f, limit=2), Rows(g))"
    want = ex.execute("i", q)
    fusion.configure(mode="on", min_hits=0)
    got = ex.execute("i", q)
    assert got == want
    dc = fusion.decision_counts()
    assert dc["ineligible"] >= 1 and dc["fused"] == 0


# ------------------------------------------------------------- EXPLAIN


def test_explain_plan_annotates_fusion_dispatch_free(corpus):
    """?explain=true marks every fusable node fused:true with the
    program-cache status, with ZERO dispatches."""
    fusion.configure(mode="on", min_hits=0)
    ex = Executor(corpus)
    q = "Count(Row(f=0)) Count(Row(f=1))"
    before = ex._stacked.dispatches
    assert ex.execute("i", q, options=ExecOptions(explain="plan")) == []
    assert ex._stacked.dispatches == before
    env = plan_mod.take_last()
    assert len(env["calls"]) == 2
    for node in env["calls"]:
        ann = node["annotations"]
        assert ann["fused"] is True
        assert ann["fusion_program"] == "uncompiled"
        assert re.fullmatch(r"[0-9a-f]{16}", ann["fusion_fingerprint"])

    ex.execute("i", q)  # compile it
    ex.execute("i", q, options=ExecOptions(explain="plan"))
    env = plan_mod.take_last()
    assert all(n["annotations"]["fusion_program"] == "cached"
               for n in env["calls"])


def test_explain_analyze_grafts_single_dispatch(corpus):
    """?explain=analyze through the fused path: the batch's ONE
    dispatch lands on the first node, zero on the rest, strategy
    'fused', and no spurious misestimate flags."""
    fusion.configure(mode="on", min_hits=0)
    ex = Executor(corpus)
    q = ("Count(Row(f=0)) Count(Intersect(Row(f=1), Row(g=10)))"
         " Count(Row(f=2))")
    ex.execute("i", q)  # warm the program
    res = ex.execute("i", q, options=ExecOptions(explain="analyze"))
    env = plan_mod.take_last()
    nodes = env["calls"]
    assert len(nodes) == len(res) == 3
    assert [n["actual"]["dispatches"] for n in nodes] == [1, 0, 0]
    assert all(n["actual"]["strategy"] == "fused" for n in nodes)
    assert all(n["actual"]["batch"] == 3 for n in nodes)
    assert all(n["annotations"]["fused"] is True for n in nodes)
    assert env["misestimates"] == 0


# --------------------------------------------------------- HTTP surface


def test_debug_fusion_over_http(tmp_path):
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        fusion.configure(mode="on", min_hits=0)
        h.client.create_index("hx")
        h.client.create_field("hx", "f")
        h.client.query("hx", "Set(1, f=10)")
        h.client.query("hx", f"Set({SHARD_WIDTH + 1}, f=10)")
        h.client.query("hx", "Count(Row(f=10)) Count(Row(f=11))")

        snap = h.client._request("GET", "/debug/fusion")
        assert snap["mode"] == "on"
        assert snap["entries"] == 1
        prog = snap["programs"][0]
        assert set(prog) >= {"fingerprint", "bucket", "calls",
                             "compile_ms", "hits", "age_seconds"}
        assert prog["calls"] == 2
        assert set(snap["decisions"]) >= {"fused", "interpreted_cold",
                                          "ineligible",
                                          "shadow_would_fuse"}

        # the index page enumerates it
        index = h.client._request("GET", "/debug")
        assert "/debug/fusion" in {e["path"] for e in index["endpoints"]}

        # /metrics counters moved
        from pilosa_tpu.utils.stats import global_stats  # noqa: PLC0415
        counters, _, _ = global_stats.snapshot()
        assert sum(v for k, v in counters.items()
                   if k[0] == "fused_dispatches_total") >= 1
    finally:
        h.close()


def test_slow_query_log_carries_fused(tmp_path):
    """SLOW QUERY pinned order gains fused= between batch= and plan=;
    an interpreted query stamps fused=0."""
    h = ServerHarness(data_dir=str(tmp_path))
    try:
        log = CaptureLogger()
        h.api.long_query_time = 0.0  # everything is slow
        h.api.logger = log
        profile_mod.clear_recent()
        h.client.create_index("sq")
        h.client.create_field("sq", "f")
        h.client.query("sq", "Set(1, f=10)")
        h.client.query("sq", f"Set({SHARD_WIDTH + 1}, f=10)")

        fusion.configure(mode="on", min_hits=0)
        h.client.query("sq", "Count(Row(f=10)) Count(Row(f=11))")
        slow = [line for line in log.lines if "SLOW QUERY" in line]
        m = re.search(r"fingerprint=[0-9a-f]{16} batch=\d+ fused=(\d+)",
                      slow[-1])
        assert m, f"pinned order broken in: {slow[-1]}"
        assert int(m.group(1)) == 2

        fusion.configure(mode="off")
        h.client.query("sq", "Count(Row(f=10)) Count(Row(f=11))")
        slow = [line for line in log.lines if "SLOW QUERY" in line]
        m = re.search(r"fused=(\d+)", slow[-1])
        assert m and int(m.group(1)) == 0
    finally:
        h.close()


# ----------------------------------------------------------------- CLI


def test_cli_config_merges_fusion_flags(tmp_path):
    """`config` prints the file < flags merge including the fusion
    knobs the server command would apply at startup."""
    import io  # noqa: PLC0415
    from contextlib import redirect_stdout  # noqa: PLC0415

    from pilosa_tpu.cli import main  # noqa: PLC0415

    import tomllib  # noqa: PLC0415

    p = tmp_path / "c.toml"
    p.write_text('fusion = "shadow"\nfusion-cache-size = 16\n')
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["config", "--config", str(p),
                   "--fusion", "on", "--fusion-min-hits", "3"])
    assert rc == 0
    cfg = tomllib.loads(buf.getvalue())
    assert cfg["fusion"] == "on"              # flag beats file
    assert cfg["fusion-cache-size"] == 16     # file survives the merge
    assert cfg["fusion-min-hits"] == 3

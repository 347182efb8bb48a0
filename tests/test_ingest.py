"""Streaming ingest engine (exec/ingest.py + the server/api.py hooks).

The load-bearing contract is FLUSH == LEGACY: with the engine on,
buffered deltas must be invisible to correctness — reads before the
merge serve the exact pre-delta snapshot (bounded staleness, no
read-path repair), and after a drain every query answers bit-for-bit
what a legacy (interval=0) server answers for the same write sequence,
across dense AND compressed container representations. Alongside: overflow back-pressure (503 + Retry-After), the
group-committed oplog watermark under fsync=interval, the crash window
between buffer and merge (subprocess + faultpoint; replay restores,
`cli check` passes), merge exclusion with the dispatch lock, the
adaptive patch-vs-rebuild pricing satellite, and /debug/ingest.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.exec import adaptive
from pilosa_tpu.exec import ingest as ingest_mod
from pilosa_tpu.exec import stacked as stacked_mod
from pilosa_tpu.ops import containers as cont
from pilosa_tpu.server import Client, PilosaHTTPServer
from pilosa_tpu.server.api import API, ServiceUnavailableError
from pilosa_tpu.server.client import ClientError
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.stats import global_stats


@pytest.fixture(autouse=True)
def _isolate():
    # CPU-scale corpora sit far below the production auto-compress
    # floor; drop it so `auto` actually chooses. Restore every global
    # knob and make sure no engine outlives its test (a registered
    # engine changes covers_pending for EVERY evaluator in-process).
    prev_mode, prev_floor = cont.repr_mode(), cont.AUTO_COMPRESS_FLOOR
    cont.AUTO_COMPRESS_FLOOR = 0
    yield
    cont.configure(prev_mode)
    cont.AUTO_COMPRESS_FLOOR = prev_floor
    cont.reset_ledger()
    adaptive.reset()
    for eng in list(ingest_mod._REGISTRY):
        eng.close()


def _mk(tmp_path, name, **api_kwargs):
    holder = Holder(str(tmp_path / name),
                    use_snapshot_queue=False).open()
    return holder, API(holder, **api_kwargs)


def _counter(name, **tags):
    key = (name, tuple(sorted(tags.items())))
    return global_stats._counters.get(key, 0)


def _normalize(res):
    out = []
    for r in res:
        cols = getattr(r, "columns", None)
        if callable(cols):
            out.append(tuple(r.columns()))
        elif hasattr(r, "pairs"):
            out.append(tuple(r.pairs))
        else:
            out.append(r)
    return out


# ------------------------------------------------- flush == legacy corpus


N_SHARDS = 2

QUERIES = (
    "Count(Row(f=1))",
    "Count(Row(f=2))",
    "Count(Row(f=3))",
    "Count(Intersect(Row(f=1), Row(f=2)))",
    "Count(Union(Row(f=2), Row(f=3)))",
    "Row(f=1)",
    "TopN(f, n=3)",
    "Count(Row(v > 50))",
)


def _base_cols(row, shard):
    base = shard * SHARD_WIDTH
    if row == 1:  # clustered -> sparse under auto
        return [base + b * 4096 + 7 * k
                for b in (3, 9) for k in range(40)]
    if row == 2:  # one long run -> rle under auto
        return list(range(base + 1000, base + 6000))
    # scattered pseudo-random -> incompressible, stays dense
    rng = np.random.default_rng(11 + shard)
    return sorted(base + c for c in
                  rng.choice(SHARD_WIDTH, size=4000, replace=False))


def _delta_cols(row, shard):
    base = shard * SHARD_WIDTH
    if row == 1:
        return [base + 20 * 4096 + 3 * k for k in range(40)]
    if row == 2:
        return list(range(base + 7000, base + 7400))
    rng = np.random.default_rng(77 + shard)
    return sorted(base + c for c in
                  rng.choice(SHARD_WIDTH, size=200, replace=False))


def _seed(api):
    api.create_index("i")
    api.create_field("i", "f")
    api.create_field("i", "v", FieldOptions.int_field(0, 1000))
    for row in (1, 2, 3):
        for shard in range(N_SHARDS):
            cols = _base_cols(row, shard)
            api.import_bits("i", "f", [row] * len(cols), cols)
    vcols = [37 * k for k in range(60)]
    api.import_values("i", "v", vcols, [k % 97 for k in range(60)])


def _delta(api):
    # every delta lands in shard 0 only: 1 of 2 shards drifts, under
    # the static patch cutoff, so the legacy pass patches (not rebuilds)
    for row in (1, 2, 3):
        cols = _delta_cols(row, 0)
        api.import_bits("i", "f", [row] * len(cols), cols)
    vcols = [37 * 60 + 11 * k for k in range(30)]
    api.import_values("i", "v", vcols, [60 + k % 37 for k in range(30)])


def _run(api):
    ex = api.executor
    return [_normalize(ex.execute("i", q)) for q in QUERIES]


@pytest.mark.parametrize("mode", ["dense", "auto"])
def test_flush_equals_legacy_differential(tmp_path, mode):
    """THE acceptance gate, twice: forced-dense (plain donated scatter
    merges) and auto (sparse/rle entries take overlay terms or interval
    rebuilds). In both, pre-merge reads serve the exact pre-delta
    snapshot with ZERO read-path patches, and post-flush answers equal
    the legacy write path's bit-for-bit."""
    cont.configure(mode)

    # -- legacy oracle: same writes, engine off, read-path repair ------
    holder_a, api_a = _mk(tmp_path, f"legacy-{mode}")
    try:
        _seed(api_a)
        _run(api_a)  # warm stacks so the delta exercises the patch path
        _delta(api_a)
        want = _run(api_a)
    finally:
        api_a.close()
        holder_a.close()

    # -- engine on: buffer, serve-stale, one interval merge ------------
    holder_b, api_b = _mk(tmp_path, f"ingest-{mode}",
                          ingest_interval=3600.0)
    try:
        eng = api_b.ingest
        assert eng is not None
        assert ingest_mod.mode() == "interval=3600s"
        _seed(api_b)
        eng.flush()  # fold the seed churn; start the window clean
        pre = _run(api_b)
        st = api_b.executor._stacked
        read0 = _counter("stacked_patches", path="read")
        stale0 = st.stale_serves

        _delta(api_b)
        snap = eng.snapshot()
        assert snap["pending"]["entries"] > 0
        assert snap["pending"]["rows"] > 0

        mid = _run(api_b)
        # Count trees serve from the device stacks: with deltas pending
        # they must answer from the exact pre-delta stack snapshot.
        # Row(f=1)/TopN extract columns per shard from host fragments
        # (no stack involved), so acked writes are visible there at
        # once — either snapshot is consistent, never a blend of a
        # patched stack.
        want_by_q0 = dict(zip(QUERIES, want))
        for q, m, p in zip(QUERIES, mid, pre):
            if q.startswith("Count"):
                assert m == p, (q, "pre-merge count left the stale "
                                "stack snapshot")
            else:
                assert m in (p, want_by_q0[q]), q
        assert _counter("stacked_patches", path="read") == read0, \
            "a read repaired a stack whose drift was pending"
        assert st.stale_serves > stale0

        merge0 = _counter("stacked_patches", path="merge")
        eng.flush()
        assert eng.snapshot()["pending"]["entries"] == 0
        assert _counter("stacked_patches", path="merge") > merge0
        assert eng.merges >= 1

        post = _run(api_b)
        assert post == want, f"mode={mode}: flush diverged from legacy"
        assert _counter("stacked_patches", path="read") == read0

        if mode == "auto":
            assert eng.overlay_entries + eng.rebuilt_entries > 0, \
                "no compressed entry went through the merge"
        from pilosa_tpu.utils import flightrec
        kinds = [e["kind"] for e in flightrec.snapshot()["events"]]
        assert "ingest.merge" in kinds
    finally:
        api_b.close()
        holder_b.close()


def test_interval_zero_is_legacy(tmp_path):
    holder, api = _mk(tmp_path, "off")
    try:
        assert api.ingest is None
        assert api.ingest_stats() == {"enabled": False,
                                      "interval_seconds": 0.0}
        assert ingest_mod.mode() == "off"
        assert not ingest_mod.covers_pending(
            "i", "f", "standard", (0,), ((1, 1),), ((1, 2),))
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", [1], [5])  # no admit/record layer
        assert api.executor.execute("i", "Count(Row(f=1))")[0] == 1
    finally:
        api.close()
        holder.close()


# --------------------------------------------- compressed overlay policy


def test_compressed_merge_overlay_then_rebuild(tmp_path):
    """A compressed entry absorbs a small merge as an overlay term (repr
    preserved — no decay to dense); past the overlay budget the interval
    rebuild re-chooses the representation. Counts stay exact at every
    step."""
    cont.configure("auto")
    holder, api = _mk(tmp_path, "ovl", ingest_interval=3600.0)
    try:
        api.create_index("i")
        api.create_field("i", "f")
        shards = 4
        for shard in range(shards):
            cols = [shard * SHARD_WIDTH + 3 * 4096 + 5 * k
                    for k in range(50)]
            api.import_bits("i", "f", [1] * len(cols), cols)
        eng = api.ingest
        eng.flush()
        ex = api.executor
        base = ex.execute("i", "Count(Row(f=1))")[0]
        st = ex._stacked

        def leaf_repr():
            return [e["repr"] for e in st.hbm_snapshot(top=50)["entries"]
                    if e["kind"] == "leaf"]

        assert leaf_repr() == ["sparse"]

        # one drifted shard of four: within the overlay budget
        api.import_bits("i", "f", [1], [123])
        eng.flush()
        assert eng.overlay_entries == 1
        assert leaf_repr() == ["sparse"], \
            "overlay merge must not decay the repr"
        assert ex.execute("i", "Count(Row(f=1))")[0] == base + 1

        # two more drifted shards: overlay_rows 1 + 2 > 4 // 2 -> rebuild
        api.import_bits("i", "f", [1, 1],
                        [SHARD_WIDTH + 77, 2 * SHARD_WIDTH + 77])
        eng.flush()
        assert eng.rebuilt_entries == 1
        assert ex.execute("i", "Count(Row(f=1))")[0] == base + 3
    finally:
        api.close()
        holder.close()


# --------------------------------------------------- overflow back-pressure


def test_overflow_backpressure_503_retry_after(tmp_path):
    holder = Holder(str(tmp_path / "bp"), use_snapshot_queue=False).open()
    api = API(holder, ingest_interval=3600.0, ingest_max_rows=10)
    server = PilosaHTTPServer(api, host="127.0.0.1", port=0)
    server.start()
    try:
        client = Client(server.address, retries=0)
        client.create_index("i")
        client.create_field("i", "f")
        # 4 points buffer 8 rows (field + _exists) — under the mark
        client.import_bits("i", "f", [1] * 4, [1, 2, 3, 4])
        with pytest.raises(ClientError) as exc:
            client.import_bits("i", "f", [1] * 4, [5, 6, 7, 8])
        assert exc.value.status == 503
        assert getattr(exc.value, "retry_after", None) is not None
        assert exc.value.retry_after >= 1
        assert api.ingest.overflows >= 1
        # in-process surface: same gate, typed error with the header.
        # (An overflow wakes the merger, which may drain the buffer at
        # any moment — so probe with a batch that overflows even an
        # empty buffer rather than racing the drain.)
        with pytest.raises(ServiceUnavailableError) as iexc:
            api._ingest_admit(1000, 0)
        assert iexc.value.headers.get("Retry-After") is not None
        # a drain releases the back-pressure
        api.ingest.flush()
        client.import_bits("i", "f", [1] * 4, [5, 6, 7, 8])

        # /debug/ingest serves the engine snapshot + the index lists it
        dbg = client._request("GET", "/debug/ingest")
        assert dbg["enabled"] is True
        assert dbg["interval_seconds"] == 3600.0
        assert dbg["overflows"] >= 1
        index = client._request("GET", "/debug")
        assert any(e["path"] == "/debug/ingest"
                   for e in index["endpoints"])
    finally:
        server.stop()
        api.close()
        holder.close()


# ------------------------------------------------- group-committed oplog


def test_group_commit_under_interval_fsync(tmp_path):
    from pilosa_tpu.storage.oplog import OpLog

    holder = Holder(str(tmp_path / "gc"), use_snapshot_queue=False).open()
    oplog = OpLog(str(tmp_path / "gc" / "oplog"),
                  fsync="interval").open()
    api = API(holder, oplog=oplog, ingest_interval=3600.0)
    try:
        api.create_index("i")
        api.create_field("i", "f")
        lag0 = oplog.summary()["replay_lag"]
        for col in (1, 2, 3):
            api.import_bits("i", "f", [1], [col])
        assert oplog.summary()["replay_lag"] == lag0 + 3, \
            "fsync=interval imports must defer mark_applied to the merge"
        api.ingest.flush()
        assert oplog.summary()["replay_lag"] == lag0
        assert api.ingest.group_commit_flushed == 3
        key = ("oplog_group_commit_records", ())
        assert global_stats._timings[key][0] >= 1
    finally:
        api.close()
        oplog.close()
        holder.close()


def test_no_group_commit_under_fsync_always(tmp_path):
    from pilosa_tpu.storage.oplog import OpLog

    holder = Holder(str(tmp_path / "ga"), use_snapshot_queue=False).open()
    oplog = OpLog(str(tmp_path / "ga" / "oplog"),
                  fsync="always").open()
    api = API(holder, oplog=oplog, ingest_interval=3600.0)
    try:
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", [1], [1])
        assert oplog.summary()["replay_lag"] == 0, \
            "fsync=always must keep the per-record applied watermark"
    finally:
        api.close()
        oplog.close()
        holder.close()


# ------------------------------------------- merge vs dispatch exclusion


def test_merge_waits_for_dispatch_lock(tmp_path):
    """The interval merge dispatches under the process-wide dispatch
    lock: while a (simulated) serving launch holds it, the drain blocks
    before any scatter — merges can never interleave with multi-device
    query dispatch."""
    cont.configure("dense")  # keep the scatter (dispatching) merge path
    holder, api = _mk(tmp_path, "lock", ingest_interval=3600.0)
    try:
        api.create_index("i")
        api.create_field("i", "f")
        for shard in range(2):
            cols = [shard * SHARD_WIDTH + c for c in range(64)]
            api.import_bits("i", "f", [1] * len(cols), cols)
        eng = api.ingest
        eng.flush()
        ex = api.executor
        ex.execute("i", "Count(Row(f=1))")  # resident 2-shard stack
        api.import_bits("i", "f", [1], [999])  # pending delta, 1 shard

        merges0 = eng.merges
        assert stacked_mod._DISPATCH_LOCK.acquire(timeout=5)
        t = threading.Thread(target=eng.flush, daemon=True)
        try:
            t.start()
            deadline = time.time() + 1.0
            while time.time() < deadline:
                assert eng.merges == merges0, \
                    "merge completed while the dispatch lock was held"
                time.sleep(0.05)
            assert t.is_alive()
        finally:
            stacked_mod._DISPATCH_LOCK.release()
        t.join(timeout=30)
        assert not t.is_alive()
        assert eng.merges == merges0 + 1
        assert eng.scatter_entries >= 1
        assert ex.execute("i", "Count(Row(f=1))")[0] == 129
    finally:
        api.close()
        holder.close()


# ------------------------------------------------- adaptive patch pricing


def test_adaptive_patch_pricing_cutoffs():
    """decide_patch prices upload vs on-device copy: with the fixed
    terms equal, the cutoff is n_changed <= 7/8 of the shards — deeper
    than the static half rule, at any stack size."""
    adaptive.reset()
    plane = 32768 * 4
    assert adaptive.decide_patch(1, 8, 1, plane)
    assert adaptive.decide_patch(7, 8, 1, plane)
    assert not adaptive.decide_patch(8, 8, 1, plane)
    assert adaptive.decide_patch(840, 960, 4, plane)
    assert not adaptive.decide_patch(841, 960, 4, plane)
    counts = adaptive.decision_counts()["patch"]
    assert counts["patch"] == 3 and counts["rebuild"] == 2
    assert adaptive.snapshot()["decisions"]["patch"] == counts


def test_changed_shards_static_vs_adaptive(tmp_path):
    """exec/stacked keeps the static half-the-shards rule with adaptive
    off (byte-identical legacy) and prices through decide_patch only
    when acting."""
    holder = Holder(str(tmp_path / "cs"), use_snapshot_queue=False).open()
    try:
        from pilosa_tpu.exec import Executor

        st = Executor(holder)._stacked
        old = tuple((1, g) for g in range(8))
        drift5 = tuple((1, g + (100 if g < 5 else 0)) for g in range(8))
        shards = tuple(range(8))
        adaptive.reset()  # mode off
        assert st._changed_shards(old, drift5, shards) is None, \
            "5/8 drift must rebuild under the static rule"
        adaptive.configure("on")
        assert st._changed_shards(old, drift5, shards) == [0, 1, 2, 3, 4]
        adaptive.configure("shadow")
        assert st._changed_shards(old, drift5, shards) is None, \
            "shadow must not change behavior"
    finally:
        adaptive.reset()
        holder.close()


# --------------------------------------------------- background interval


def test_background_merge_fires_on_interval(tmp_path):
    holder, api = _mk(tmp_path, "bg", ingest_interval=0.1)
    try:
        api.create_index("i")
        api.create_field("i", "f")
        api.import_bits("i", "f", [1], [42])
        eng = api.ingest
        deadline = time.time() + 10
        while time.time() < deadline and eng.merges == 0:
            time.sleep(0.05)
        assert eng.merges >= 1, "interval merger never drained"
        assert eng.snapshot()["pending"]["entries"] == 0
    finally:
        api.close()
        holder.close()


# --------------------------------------------------- crash window (proc)


@pytest.mark.skipif(
    os.environ.get("PILOSA_TPU_PROC_TESTS", "1") == "0",
    reason="process cluster tests disabled")
def test_crash_between_buffer_and_merge():
    """Kill a real server at ingest.pre-merge — deltas buffered, merge
    not run. Acked writes are already WAL-durable + host-applied, so the
    restarted server serves every acked column and the fragment files
    pass `cli check`. This is the crash-semantics half of the tentpole:
    the device stack cache is the ONLY thing a crash loses."""
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    datadir = tempfile.mkdtemp(prefix="pilosa-ingest-crash-")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    logpath = os.path.join(datadir, "server.log")
    client = Client(f"http://127.0.0.1:{port}", timeout=30, retries=0)

    def spawn():
        log = open(logpath, "a")
        proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server",
             "--bind", f"127.0.0.1:{port}",
             "--data-dir", datadir,
             "--fsync", "always",
             "--ingest-merge-interval", "200ms"],
            stdout=log, stderr=subprocess.STDOUT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=cwd)
        log.close()
        return proc

    def wait_ready(proc, timeout=60):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(f"server exited rc={proc.returncode}")
            try:
                client._request("GET", "/status")
                return
            except Exception:
                time.sleep(0.25)
        raise TimeoutError("server not ready")

    proc = spawn()
    try:
        wait_ready(proc)
        client.create_index("cw")
        client.create_field("cw", "f")
        dbg = client._request("GET", "/debug/ingest")
        assert dbg["enabled"] is True
        client._request("POST", "/debug/faultpoints", json.dumps(
            {"arm": ["ingest.pre-merge=exit"]}).encode())
        acked = []
        for col in (11, 12, 13):
            try:
                client.import_bits("cw", "f", [1], [col])
                acked.append(col)
            except Exception:
                break  # the armed exit can fire between imports
        assert acked, "no import was acked before the crash"
        # the next 200ms tick drains the buffer and trips the exit
        from pilosa_tpu.utils.faultpoints import EXIT_CODE

        rc = proc.wait(timeout=60)
        assert rc == EXIT_CODE, f"expected fault exit, rc={rc}"

        proc = spawn()
        wait_ready(proc)
        res = client.query("cw", "Row(f=1)")
        got = set(res["results"][0]["columns"])
        assert set(acked) <= got, f"lost acked writes: {set(acked) - got}"

        proc.terminate()
        proc.wait(timeout=10)
        from pilosa_tpu.cli import main as cli_main

        frag_files = []
        for root, _dirs, files in os.walk(datadir):
            frag_files += [os.path.join(root, fn) for fn in files
                           if fn.isdigit()]
        assert frag_files, "no fragment files found"
        assert cli_main(["check", *frag_files]) == 0
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        shutil.rmtree(datadir, ignore_errors=True)

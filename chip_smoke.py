#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

Drives the system's main path once through the entry points a user calls
(`make -C native`, `python -m pilosa_tpu.cli server`, the HTTP client) at
the size of BASELINE config 2 — a 954-shard (1B-column) index resident in
HBM — and compares every answer with a numpy oracle computed here from the
same seed. It checks answers, not speed: the times it prints are set-up
information, never metrics.

One process owns the chip at a time. This parent never imports JAX; each
phase that needs the device is a child process, run one after another:

  kernels  every public function of ops/pallas_kernels.py compiled with
           interpret=False at the serving shapes, bit for bit against jnp
  build    native/libpilosa_native.so deleted and rebuilt from source
  serve    `cli server --fsync interval` on an empty data dir; `north`
           (954 shards, 2 fields x 2 rows, every plane distinct) and
           `mixed` (10 shards: two set fields, an int field, a time field)
           loaded over HTTP through the import APIs that carry the oplog
           ack; Count/Intersect/Union/Difference/Xor/3-leaf tree, 32
           concurrent Counts, explain=analyze, acknowledged writes read
           back, TopN/GroupBy/Sum/Min/Max/BSI range/time range
  restart  SIGINT, wait, start again on the same data and cache dir: same
           answers, acknowledged writes included, compile cache re-used

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
(the device as the server's /info reports it) only if every phase passed.
Without an accelerator the children refuse to boot (pilosa_tpu/utils/
device.py) and this exits non-zero without that line.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --devices 4      # one server driving four chips
    python chip_smoke.py --layout spmd --devices 4   # four --spmd servers
    python chip_smoke.py --platform cpu   # dry run: tiny sizes, host CPU
"""

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(REPO, ".smoke")
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
NATIVE_DIR = os.path.join(REPO, "native")

#: whole-run budget in seconds (the contract allows 1200, compile included)
BUDGET_S = 1150
WORD_DENSITY = 0.05         # share of non-zero words per north plane
#: sizes per --platform. tpu is the real thing: north = ceil(1e9 / 2^20)
#: shards (BASELINE config 2), mixed = BASELINE configs 3/4. cpu is the
#: dry run the tests drive; every cut it makes is printed under `reduced`.
SIZES = {
    "tpu": {"north_shards": 954, "mixed_shards": 10,
            "mixed_per_shard": 20_000, "spmd_shards": 64,
            "blocks": (65536, 65531), "topn_rows": (64, 100),
            "pairwise_rows": (16, 20)},
    "cpu": {"north_shards": 8, "mixed_shards": 2,
            "mixed_per_shard": 2_000, "spmd_shards": 8,
            "blocks": (64, 61), "topn_rows": (10,),
            "pairwise_rows": (4, 5)},
}


# --------------------------------------------------------------- reporting


class Smoke:
    """Run state: what the children reported, what failed, what to stop."""

    def __init__(self, args):
        self.args = args
        self.device = None      # {"platform", "kind", "count"} once reported
        self.failures = []
        self.procs = []
        self.reduced = []
        self.setup = {}         # set-up information (NOT metrics)
        self.t0 = time.monotonic()

    def say(self, phase, msg):
        d = self.device
        tag = (f"platform={d['platform']} kind={d['kind']!r} "
               f"count={d['count']}") if d else "device=not-yet-reported"
        print(f"smoke [{tag}] {phase}: {msg}", flush=True)

    def fail(self, phase, msg):
        self.failures.append(f"{phase}: {msg}")
        self.say(phase, f"FAIL {msg}")

    def check(self, phase, name, got, want):
        if got == want:
            self.say(phase, f"ok   {name} = {_short(got)}")
            return True
        self.fail(phase, f"{name}: got {_short(got)}, oracle {_short(want)}")
        return False

    def note_device(self, facts, phase):
        dev = {"platform": facts["platform"], "kind": facts["deviceKind"],
               "count": facts["deviceCount"]}
        if self.device is not None and dev != self.device:
            self.fail(phase, f"device changed: {self.device} -> {dev}")
        self.device = dev
        want = self.args.devices
        if want is not None and dev["count"] != want:
            self.fail(phase, f"{dev['count']} device(s) reported, "
                             f"--devices {want} expected")

    def remaining(self):
        return BUDGET_S - (time.monotonic() - self.t0)

    def child_env(self):
        """Environment of every child. Without --platform cpu the host
        CPU must never be chosen, so an inherited JAX_PLATFORMS (this
        sandbox exports JAX_PLATFORMS=cpu) is dropped and the children's
        boot demands a TPU."""
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        if self.args.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        return env

    def spawn(self, argv, log_name, env=None):
        """Start a child in its own session with output to a log file."""
        os.makedirs(LOG_DIR, exist_ok=True)
        log = open(os.path.join(LOG_DIR, log_name), "wb")
        proc = subprocess.Popen(
            argv, cwd=REPO, env=env or self.child_env(), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
        proc.log_path = log.name
        log.close()
        self.procs.append(proc)
        return proc

    def stop_all(self):
        for proc in self.procs:
            if proc.poll() is None:
                _signal_group(proc, signal.SIGTERM)
        deadline = time.monotonic() + 10
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    proc.wait(timeout=max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    _signal_group(proc, signal.SIGKILL)
                    proc.wait()


def _signal_group(proc, sig):
    try:
        os.killpg(proc.pid, sig)
    except (ProcessLookupError, PermissionError):
        pass


def _short(v, n=160):
    s = v if isinstance(v, str) else repr(v)
    return s if len(s) <= n else s[:n] + f"... ({len(s)} chars)"


def _tail(path, n=60):
    try:
        with open(path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(
                "utf-8", "replace")
    except OSError as e:
        return f"<{e}>"


# ------------------------------------------------------------ numpy oracle


def popcount(words):
    import numpy as np

    return int(np.bitwise_count(words).sum(dtype=np.int64))


def north_planes(seed, n_shards):
    """{(field, row): [n_shards, WORDS_PER_ROW] uint32} — every shard's
    plane drawn independently (all distinct), ~WORD_DENSITY of the words
    non-zero so the roaring import stays tractable while nearly every
    128-word block is occupied (the container chooser keeps them dense:
    4 x 119 MiB of leaf stacks at 954 shards)."""
    import numpy as np

    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    rng = np.random.default_rng([seed, 1])
    planes = {}
    for field in ("f", "g"):
        for row in (1, 2):
            words = rng.integers(0, 1 << 32, (n_shards, WORDS_PER_ROW),
                                 dtype=np.uint32)
            keep = rng.random((n_shards, WORDS_PER_ROW),
                              dtype=np.float32) < WORD_DENSITY
            planes[field, row] = np.where(keep, words, np.uint32(0))
    return planes


def north_oracle(planes):
    """PQL -> expected result over the north planes."""
    f1, f2 = planes["f", 1], planes["f", 2]
    g1, g2 = planes["g", 1], planes["g", 2]
    out = {
        "Count(Row(f=1))": popcount(f1),
        "Count(Row(f=2))": popcount(f2),
        "Count(Row(g=1))": popcount(g1),
        "Count(Row(g=2))": popcount(g2),
        "Count(Union(Row(f=1), Row(g=2)))": popcount(f1 | g2),
        "Count(Difference(Row(f=2), Row(g=1)))": popcount(f2 & ~g1),
        "Count(Xor(Row(f=2), Row(g=2)))": popcount(f2 ^ g2),
        "Count(Intersect(Union(Row(f=1), Row(f=2)), Row(g=1)))":
            popcount((f1 | f2) & g1),
    }
    for a in (1, 2):
        for b in (1, 2):
            out[f"Count(Intersect(Row(f={a}), Row(g={b})))"] = popcount(
                planes["f", a] & planes["g", b])
    return out


def mixed_data(seed, n_shards, per_shard):
    """One record per column, star-schema style: set attributes a (16
    rows) and b (20 rows), an int measure v, and for a quarter of the
    columns a time-stamped event row t on one of 90 days."""
    import numpy as np

    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng([seed, 2])
    cols = np.concatenate([
        s * SHARD_WIDTH + np.sort(rng.choice(SHARD_WIDTH, per_shard,
                                             replace=False))
        for s in range(n_shards)]).astype(np.int64)
    n = len(cols)
    has_t = rng.random(n) < 0.25
    return {
        "cols": cols,
        "a": rng.integers(0, 16, n),
        "b": rng.integers(0, 20, n),
        "v": rng.integers(-5000, 1_000_000, n),
        "has_t": has_t,
        "t_row": rng.integers(0, 4, n),
        "t_day": rng.integers(0, 90, n),
    }


def _day(i):
    import datetime

    return (datetime.date(2019, 1, 1)
            + datetime.timedelta(days=int(i))).strftime("%Y-%m-%dT00:00")


def mixed_oracle(d):
    """[(PQL, expected, normalise)] over the mixed records; `normalise`
    maps the server's JSON result to the oracle's shape."""
    import numpy as np

    a, b, v, cols = d["a"], d["b"], d["v"], d["cols"]

    def topn(counts, n):
        order = sorted(((int(c), r) for r, c in enumerate(counts) if c),
                       key=lambda t: (-t[0], t[1]))[:n]
        return [(r, c) for c, r in order]

    def norm_topn(res):
        return sorted(((p["id"], p["count"]) for p in res),
                      key=lambda t: (-t[1], t[0]))

    def norm_sum(res):
        return (res["value"], res["count"])

    groups = {}
    pair_counts = np.bincount(a * 20 + b, minlength=320)
    for k, c in enumerate(pair_counts):
        if c:
            groups[(k // 20, k % 20)] = int(c)

    def norm_groups(res):
        return {(g["group"][0]["rowID"], g["group"][1]["rowID"]): g["count"]
                for g in res}

    k_hi, k_lo = 250_000, -1_000
    sel = a == 3
    present = int(v[len(v) // 2])
    lo_d, hi_d = 9, 50       # [2019-01-10, 2019-02-20)
    t_sel = d["has_t"] & (d["t_row"] == 1)
    in_range = t_sel & (d["t_day"] >= lo_d) & (d["t_day"] < hi_d)
    narrow = t_sel & (d["t_day"] >= 30) & (d["t_day"] < 33)
    ident = lambda r: r  # noqa: E731
    return [
        ("TopN(a, n=5)", topn(np.bincount(a, minlength=16), 5), norm_topn),
        ("TopN(b, Row(a=3), n=4)",
         topn(np.bincount(b[sel], minlength=20), 4), norm_topn),
        ("GroupBy(Rows(a), Rows(b))", groups, norm_groups),
        ("Sum(field=v)", (int(v.sum()), len(v)), norm_sum),
        ("Sum(Row(a=3), field=v)", (int(v[sel].sum()), int(sel.sum())),
         norm_sum),
        ("Min(field=v)", (int(v.min()), int((v == v.min()).sum())),
         norm_sum),
        ("Max(field=v)", (int(v.max()), int((v == v.max()).sum())),
         norm_sum),
        (f"Count(Row(v > {k_hi}))", int((v > k_hi).sum()), ident),
        (f"Count(Row(v <= {k_lo}))", int((v <= k_lo).sum()), ident),
        (f"Count(Row(v == {present}))", int((v == present).sum()), ident),
        (f"Count(Intersect(Row(a=3), Row(v > {k_hi})))",
         int((sel & (v > k_hi)).sum()), ident),
        (f'Count(Row(t=1, from="{_day(lo_d)}", to="{_day(hi_d)}"))',
         int(in_range.sum()), ident),
        (f'Row(t=1, from="{_day(30)}", to="{_day(33)}")',
         [int(c) for c in cols[narrow]], lambda r: r["columns"]),
    ]


# ------------------------------------------------------------------ phases


def phase_kernels(sm):
    """Child process: compile + check every Pallas kernel. The child
    prints one JSON line per fact; everything it prints is in its log."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", "kernels",
            "--seed", str(sm.args.seed), "--platform", sm.args.platform]
    proc = sm.spawn(argv, "kernels.log")
    try:
        proc.wait(timeout=max(30, sm.remaining()))
    except subprocess.TimeoutExpired:
        sm.fail("kernels", "timed out")   # stop_all() kills it
        return
    n_ok = n = 0
    with open(proc.log_path, errors="replace") as f:
        lines = f.read().splitlines()
    for line in lines:
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if "device" in rec:
            sm.note_device(rec["device"], "kernels")
        elif "kernel" in rec:
            n += 1
            if rec["ok"]:
                n_ok += 1
                sm.say("kernels", f"ok   {rec['kernel']} "
                                  f"interpret={rec['interpret']}")
            else:
                sm.fail("kernels", f"{rec['kernel']}: {rec['error']}")
    if proc.returncode != 0 and n_ok == n:
        sm.fail("kernels", f"child exited {proc.returncode}:\n"
                           + _tail(proc.log_path, 15))
    elif n == 0:
        sm.fail("kernels", "child reported no kernel")
    else:
        sm.say("kernels", f"{n_ok}/{n} kernel checks passed")


def phase_build(sm):
    """Delete and rebuild the native library; the servers load it. Under
    the lock pilosa_tpu/native.py builds under: another process's first
    import must not run the same make between the unlink and the rename."""
    import fcntl

    with open(os.path.join(NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        for name in os.listdir(NATIVE_DIR):
            if name.startswith("libpilosa_native.so"):
                os.unlink(os.path.join(NATIVE_DIR, name))
        res = subprocess.run(["make", "-C", NATIVE_DIR],
                             capture_output=True, text=True, timeout=300)
    so = os.path.join(NATIVE_DIR, "libpilosa_native.so")
    if res.returncode != 0 or not os.path.exists(so):
        sm.fail("build", f"make -C native exited {res.returncode}: "
                         f"{res.stderr[-800:]}")
        return False
    from pilosa_tpu import native

    if not native.enabled():
        sm.fail("build", "rebuilt libpilosa_native.so does not load")
        return False
    sm.say("build", f"rebuilt {os.path.relpath(so, REPO)} "
                    f"({os.path.getsize(so)} bytes) from pilosa_native.cpp")
    return True


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_server(sm, port, log_name, data_dir=DATA_DIR, extra=(), env=None,
                 timeout=600):
    """`python -m pilosa_tpu.cli server` with default flags plus
    --fsync interval; returns (proc, client) without waiting."""
    from pilosa_tpu.server.client import Client

    argv = [sys.executable, "-m", "pilosa_tpu.cli", "server",
            "--bind", f"127.0.0.1:{port}", "--data-dir", data_dir,
            "--fsync", "interval", *extra]
    return (sm.spawn(argv, log_name, env=env),
            Client(f"http://127.0.0.1:{port}", timeout=timeout, retries=0))


def wait_ready(sm, servers):
    """Block until every (proc, client) answers /status; seconds waited."""
    t0 = time.monotonic()
    limit = min(300, max(30, sm.remaining()))
    pending = list(servers)
    while pending:
        proc, client = pending[0]
        if proc.poll() is not None:
            raise RuntimeError(
                f"server exited {proc.returncode} before serving:\n"
                + _tail(proc.log_path, 25))
        try:
            client.status()
            pending.pop(0)
            continue
        except OSError:
            pass
        if time.monotonic() - t0 > limit:
            raise RuntimeError("server did not answer /status:\n"
                               + _tail(proc.log_path, 25))
        time.sleep(0.5)
    return time.monotonic() - t0


def stop_servers(sm, procs, phase):
    """SIGINT is the graceful path (cli.cmd_server's finally block):
    signal all, then every one must exit 0 with a clean log."""
    for proc in procs:
        proc.send_signal(signal.SIGINT)
    ok = True
    for proc in procs:
        try:
            rc = proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            rc = "nothing within 180 s"
        if rc != 0:
            sm.fail(phase, f"server answered SIGINT with {rc}:\n"
                           + _tail(proc.log_path, 30))
            ok = False
        else:
            ok &= check_server_log(sm, proc, phase)
    return ok


def check_server_log(sm, proc, phase):
    """The server's own log (timestamped StandardLogger lines) may not say
    it failed at something, print a traceback, or fall back from the
    native library to the Python loops."""
    import re

    stamp = re.compile(r"^\d{4}-\d\d-\d\dT\d\d:\d\d:\d\d ")
    with open(proc.log_path, errors="replace") as f:
        bad = [line.rstrip() for line in f
               if "Traceback (most recent call last)" in line
               or "native library unavailable" in line
               or (stamp.match(line) and re.search(
                   r"fail|error|exception", line, re.I))]
    for line in bad[:5]:
        sm.fail(phase, f"server log: {line[:300]}")
    return not bad


def cache_entries():
    from pilosa_tpu.utils import device

    try:
        return len(os.listdir(device.cache_dir()))
    except FileNotFoundError:
        return 0


def north_blob(planes, field, shard):
    """Serialized roaring of one (field, shard) fragment: rows 1 and 2.
    optimize=False keeps the array containers the planes convert to —
    re-encoding these random bits as run containers makes the server's
    merge several times slower (one native call per run)."""
    from pilosa_tpu.roaring import Bitmap, serialize
    from pilosa_tpu.shardwidth import CONTAINERS_PER_SHARD

    bitmap = Bitmap()
    for row in (1, 2):
        bitmap.replace_dense_words(
            row * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD,
            planes[field, row][shard])
    return serialize(bitmap, optimize=False)


def load_north(sm, client, planes):
    """import_roaring, one request per (field, shard) carrying both rows,
    one after another (concurrent imports only contend in the server)."""
    client.create_index("north")
    for field in ("f", "g"):
        client.create_field("north", field)
    changed = 0
    for shard in range(planes["f", 1].shape[0]):
        for field in ("f", "g"):
            changed += client.import_roaring(
                "north", field, shard,
                north_blob(planes, field, shard))["changed"]
    want = sum(popcount(p) for p in planes.values())
    sm.check("serve", "north bits acknowledged by import_roaring",
             changed, want)


def load_mixed(sm, client, d):
    """import_bits / import_values in 100k-record requests."""
    client.create_index("mixed")
    client.create_field("mixed", "a")
    client.create_field("mixed", "b")
    client.create_field("mixed", "v", {
        "type": "int", "min": -5000, "max": 1_000_000})
    client.create_field("mixed", "t", {
        "type": "time", "timeQuantum": "YMD"})
    cols = d["cols"]
    step = 100_000
    for lo in range(0, len(cols), step):
        sl = slice(lo, lo + step)
        c = cols[sl].tolist()
        client.import_bits("mixed", "a", d["a"][sl].tolist(), c)
        client.import_bits("mixed", "b", d["b"][sl].tolist(), c)
        client.import_values("mixed", "v", c, d["v"][sl].tolist())
        ht = d["has_t"][sl]
        if ht.any():
            client.import_bits(
                "mixed", "t", d["t_row"][sl][ht].tolist(),
                cols[sl][ht].tolist(),
                timestamps=[_day(i) for i in d["t_day"][sl][ht]])


def run_queries(sm, client, phase, index, cases):
    """cases: [(pql, expected, normalise)]; True when all agree."""
    ok = True
    for pql, want, norm in cases:
        try:
            got = norm(client.query(index, pql)["results"][0])
        except Exception as e:  # noqa: BLE001 — report, keep checking
            sm.fail(phase, f"{index}: {pql}: {type(e).__name__}: {e}")
            ok = False
            continue
        ok &= sm.check(phase, f"{index}: {pql}", got, want)
    return ok


def north_cases(oracle):
    return [(pql, want, lambda r: r) for pql, want in oracle.items()]


def stacked_stats(client):
    return client._request("GET", "/debug/vars")["stacked"]


def concurrent_counts(sm, client_factory, phase, oracle):
    """32 Counts from 32 threads at once: the group-commit path
    (exec/stacked._batched_count). Must agree and must not wedge."""
    pqls = [f"Count(Intersect(Row(f={a}), Row(g={b})))"
            for a in (1, 2) for b in (1, 2)] * 8
    got = [None] * len(pqls)
    barrier = threading.Barrier(len(pqls))

    def one(i):
        c = client_factory()
        barrier.wait(timeout=60)
        try:
            got[i] = c.query("north", pqls[i])["results"][0]
        except Exception as e:  # noqa: BLE001 — compared below
            got[i] = f"{type(e).__name__}: {e}"

    before = stacked_stats(client_factory())
    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(pqls))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 300
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    if any(t.is_alive() for t in threads):
        sm.fail(phase, "concurrent Counts wedged (threads still waiting "
                       "after 300 s)")
        return
    after = stacked_stats(client_factory())
    sm.check(phase, "32 concurrent Counts", got, [oracle[p] for p in pqls])
    batches = after["count_batches"] - before["count_batches"]
    batched = (after["count_batched_queries"]
               - before["count_batched_queries"])
    sm.say(phase, f"group commit: {batched} queries in {batches} "
                  f"device batches")


def check_explain(sm, client, phase):
    """A warm north Count must be ONE stacked dispatch — 954 per-shard
    dispatches that answer correctly are a failure."""
    pql = "Count(Intersect(Row(f=1), Row(g=1)))"
    client.query("north", pql)
    plan = client.query("north", pql, explain="analyze")["plan"]
    call = plan["calls"][0]
    got = (call.get("strategy"), call.get("actual", {}).get("dispatches"))
    sm.check(phase, f"explain=analyze {pql} (strategy, dispatches)",
             got, ("stacked", 1))


def acknowledged_writes(sm, client, planes, n_shards):
    """One PQL Set and one import_bits batch, acknowledged, then read
    back through a Count; the resident stack must be patched (only the
    touched shards re-uploaded), not rebuilt."""
    import numpy as np

    from pilosa_tpu.shardwidth import SHARD_WIDTH

    rng = np.random.default_rng([sm.args.seed, 3])
    # Set: first clear bit of f/1 in a middle shard
    shard = n_shards // 2
    word = int(np.flatnonzero(planes["f", 1][shard] == 0)[0])
    col = shard * SHARD_WIDTH + word * 32
    before = stacked_stats(client)
    ack = client.query("north", f"Set({col}, f=1)")["results"][0]
    sm.check("serve", f"Set({col}, f=1) acknowledged", ack, True)
    planes["f", 1][shard, word] |= np.uint32(1)
    sm.check("serve", "north: Count(Row(f=1)) after Set",
             client.query("north", "Count(Row(f=1))")["results"][0],
             popcount(planes["f", 1]))
    mid = stacked_stats(client)
    _check_patched(sm, "Set", before, mid, 1)

    # import_bits: 500 bits of g/2 spread over two shards
    touched = sorted({n_shards // 3, (2 * n_shards) // 3})
    offs = rng.choice(SHARD_WIDTH, 250 * len(touched), replace=False)
    cols = np.concatenate([
        s * SHARD_WIDTH + offs[i * 250:(i + 1) * 250]
        for i, s in enumerate(touched)])
    client.import_bits("north", "g", [2] * len(cols), cols.tolist())
    flat = planes["g", 2].reshape(-1)
    np.bitwise_or.at(flat, cols // 32,
                     (np.uint32(1) << (cols % 32).astype(np.uint32)))
    sm.check("serve", "north: Count(Row(g=2)) after import_bits",
             client.query("north", "Count(Row(g=2))")["results"][0],
             popcount(planes["g", 2]))
    _check_patched(sm, "import_bits", mid, stacked_stats(client),
                   len(touched))


def _check_patched(sm, what, before, after, touched):
    patches = after["patches"] - before["patches"]
    uploaded = after["planes_uploaded"] - before["planes_uploaded"]
    sm.check("serve", f"{what}: (stack patches, planes re-uploaded)",
             (patches, uploaded), (1, touched))


def check_spread(sm, client, phase):
    """Every device must hold its share of north's stack bytes: four
    chips, not everything on device 0."""
    hbm = client.debug_hbm(top=0)
    north = sum(e["bytes"] for e in hbm["by_index_field"]
                if e["index"] == "north")
    mem = hbm.get("device_memory")
    count = sm.device["count"]
    if sm.device["platform"] == "cpu":
        sm.say(phase, f"north stack bytes {north}; per-device spread not "
                      f"checked (the CPU backend reports no memory_stats)")
        return
    if not mem or len(mem) != count:
        sm.fail(phase, f"/debug/hbm device_memory lists "
                       f"{len(mem or [])} device(s), {count} expected")
        return
    used = [m.get("bytes_in_use", 0) for m in mem]
    sm.say(phase, f"north stack bytes {north}; bytes_in_use per device "
                  f"{used}")
    if min(used) < 0.9 * north / count:
        sm.fail(phase, f"a device holds less than its share of north "
                       f"({min(used)} < 0.9 * {north} / {count})")
    if max(used) > 1.25 * min(used):
        sm.fail(phase, f"device memory uneven: {used}")


def phase_serve(sm):
    """serve + restart on one server process driving every local chip."""
    import numpy as np

    from pilosa_tpu.server.client import Client

    size, full = SIZES[sm.args.platform], SIZES["tpu"]
    n_north, n_mixed = size["north_shards"], size["mixed_shards"]
    per_shard = size["mixed_per_shard"]
    if size != full:
        sm.reduced += [
            f"north: {n_north} shards instead of {full['north_shards']} "
            f"(--platform cpu dry run on the host)",
            f"mixed: {n_mixed} shards x {per_shard} records instead of "
            f"{full['mixed_shards']} x {full['mixed_per_shard']} "
            f"(--platform cpu dry run)"]
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    os.makedirs(DATA_DIR)
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    cache0 = cache_entries()
    proc, client = start_server(sm, port, "server.boot1.log")
    boot_s = wait_ready(sm, [(proc, client)])
    sm.note_device(client.info(), "serve")
    sm.setup["boot1_ready_s"] = round(boot_s, 1)
    sm.say("serve", f"server up in {boot_s:.1f} s on an empty data dir")

    planes = north_planes(sm.args.seed, n_north)
    mixed = mixed_data(sm.args.seed, n_mixed, per_shard)
    t0 = time.monotonic()
    load_north(sm, client, planes)
    load_mixed(sm, client, mixed)
    sm.setup["load_s"] = round(time.monotonic() - t0, 1)
    sm.say("serve", f"loaded north ({n_north} shards = "
                    f"{n_north << 20} columns, 4 planes of "
                    f"{planes['f', 1].nbytes >> 20} MiB) and mixed "
                    f"({n_mixed} shards, {len(mixed['cols'])} records) "
                    f"over HTTP in {sm.setup['load_s']} s")

    oracle = north_oracle(planes)
    t0 = time.monotonic()
    first = client.query("north", "Count(Row(f=1))")["results"][0]
    sm.setup["boot1_first_query_s"] = round(time.monotonic() - t0, 2)
    sm.check("serve", "north: first query Count(Row(f=1))", first,
             oracle["Count(Row(f=1))"])
    run_queries(sm, client, "serve", "north", north_cases(oracle))
    warm = []
    for _ in range(9):
        t0 = time.monotonic()
        client.query("north", "Count(Intersect(Row(f=1), Row(g=1)))")
        warm.append(time.monotonic() - t0)
    sm.setup["warm_query_ms_median_of_9"] = round(
        float(np.median(warm)) * 1000, 2)
    concurrent_counts(
        sm, lambda: Client(base, timeout=600, retries=0), "serve", oracle)
    check_explain(sm, client, "serve")
    check_spread(sm, client, "serve")
    acknowledged_writes(sm, client, planes, n_north)
    oracle = north_oracle(planes)
    run_queries(sm, client, "serve", "mixed", mixed_oracle(mixed))
    cache1 = cache_entries()
    stop_servers(sm, [proc], "serve")
    if sm.failures:
        sm.say("restart", "skipped: an earlier phase failed")
        return

    # ---- restart on the same data dir and cache dir
    proc, client = start_server(sm, port, "server.boot2.log")
    boot_s = wait_ready(sm, [(proc, client)])
    sm.note_device(client.info(), "restart")
    sm.setup["boot2_ready_s"] = round(boot_s, 1)
    sm.say("restart", f"server back in {boot_s:.1f} s on the same data dir")
    t0 = time.monotonic()
    first = client.query("north", "Count(Row(f=1))")["results"][0]
    sm.setup["boot2_first_query_s"] = round(time.monotonic() - t0, 2)
    sm.check("restart", "north: first query Count(Row(f=1)) "
                        "(acknowledged Set included)", first,
             oracle["Count(Row(f=1))"])
    run_queries(sm, client, "restart", "north", north_cases(oracle))
    concurrent_counts(
        sm, lambda: Client(base, timeout=600, retries=0), "restart", oracle)
    check_explain(sm, client, "restart")
    run_queries(sm, client, "restart", "mixed", mixed_oracle(mixed))
    cache2 = cache_entries()
    stop_servers(sm, [proc], "restart")
    gained1, gained2 = cache1 - cache0, cache2 - cache1
    sm.setup["compile_cache_entries"] = {
        "before": cache0, "gained_boot1": gained1, "gained_boot2": gained2}
    sm.say("restart", f"compile cache: {cache0} entries before, "
                      f"+{gained1} in the first boot, +{gained2} in the "
                      f"second")
    if cache1 == 0:
        sm.fail("restart", "the first boot wrote nothing to the compile "
                           "cache")
    if gained2 > max(3, gained1 // 4):
        sm.fail("restart", f"the second boot compiled {gained2} new "
                           f"programs (first boot: {gained1}): the "
                           f"compile cache was not re-used")


# ------------------------------------------------------ the --spmd layout


def phase_spmd(sm):
    """The other four-chip layout: N `cli server --spmd` processes, one
    chip each through the environment libtpu reads, Counts merged over
    the collective plane. Passes when `steps` in /internal/spmd/stats
    advance on every node and the answers agree with the oracle."""
    n = sm.args.devices or 4
    tiny = sm.args.platform == "cpu"
    n_shards = SIZES[sm.args.platform]["spmd_shards"]
    sm.reduced.append(
        f"--layout spmd: north cut to {n_shards} shards (the layout's "
        f"question is whether collectives cross {n} processes on real "
        f"chips; four chips cost four times the chip budget per minute)")
    ports = [_free_port() for _ in range(n)]
    hosts = ",".join(f"127.0.0.1:{p}" for p in ports)
    spmd_port = _free_port()
    tpu_port0 = _free_port()
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    servers = []
    side = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "2,4,1"}.get(n)
    for i, port in enumerate(ports):
        env = sm.child_env()
        extra = ["--cluster-hosts", hosts, "--replicas", "1", "--spmd",
                 "--spmd-port", str(spmd_port)]
        if tiny:
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
            extra += ["--spmd-cpu-collectives", "gloo"]
        else:
            env.update({
                "TPU_VISIBLE_CHIPS": str(i),
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": side,
                "TPU_PROCESS_ADDRESSES": ",".join(
                    f"localhost:{tpu_port0 + k}" for k in range(n)),
                "TPU_PROCESS_PORT": str(tpu_port0 + i),
                "CLOUD_TPU_TASK_ID": str(i),
            })
        data = os.path.join(DATA_DIR, f"node{i}")
        os.makedirs(data)
        # a collective that hangs must cost one short timeout
        servers.append(start_server(
            sm, port, f"spmd.node{i}.log", data_dir=data, extra=extra,
            env=env, timeout=180))
    wait_ready(sm, servers)
    procs, clients = zip(*servers)
    infos = [c.info() for c in clients]
    sm.note_device(infos[0], "spmd")
    sm.say("spmd", f"{n} nodes up; local devices per node "
                   f"{[i['localDeviceCount'] for i in infos]}, global "
                   f"{[i['deviceCount'] for i in infos]}")
    if any(i["localDeviceCount"] != 1 or i["deviceCount"] != n
           for i in infos):
        sm.fail("spmd", "each node must own one chip of a global mesh "
                        f"of {n}")
        return
    planes = north_planes(sm.args.seed, n_shards)
    clients[0].create_index("north")
    for field in ("f", "g"):
        clients[0].create_field("north", field)
    time.sleep(1.0)  # schema broadcast
    for shard in range(n_shards):
        for field in ("f", "g"):
            clients[0].import_roaring("north", field, shard,
                                      north_blob(planes, field, shard))
    oracle = north_oracle(planes)

    def steps():
        return [c._request("GET", "/internal/spmd/stats").get("steps", 0)
                for c in clients]

    before = steps()
    # drive a NON-coordinator node so the step has to be forwarded; one
    # query first, so a collective that hangs costs one timeout, not 12
    cases = north_cases(oracle)
    if run_queries(sm, clients[-1], "spmd", "north", cases[:1]):
        run_queries(sm, clients[-1], "spmd", "north", cases[1:])
    after = steps()
    sm.say("spmd", f"collective steps per node before {before} after "
                   f"{after}")
    if not all(a > b for a, b in zip(after, before)):
        sm.fail("spmd", "`steps` did not advance on every node — the "
                        "Counts did not ride the collective plane")
    stop_servers(sm, procs, "spmd")


# ---------------------------------------------------- child: kernels phase


def child_kernels(args):
    """Runs in its own process (it owns the chip). One JSON line per
    check on stdout; tracebacks go to chiprun_out/chip_smoke/."""
    from pilosa_tpu.utils import device

    facts = device.boot()
    print(json.dumps({"device": facts}), flush=True)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pilosa_tpu.ops import bitplane, bsi
    from pilosa_tpu.ops import pallas_kernels as pk
    from pilosa_tpu.parallel.sharded import _count_expr_fn
    from pilosa_tpu.shardwidth import WORDS_PER_ROW as W

    size = SIZES[args.platform]
    interpret = pk._interpret()
    if interpret != (facts["platform"] != "tpu"):
        raise SystemExit("kernels: interpret mode disagrees with the "
                         "booted platform")
    key = [jax.random.PRNGKey(args.seed)]

    def bits(*shape):
        key[0], sub = jax.random.split(key[0])
        return jax.random.bits(sub, shape, dtype=jnp.uint32)

    def same(got, want):
        got, want = np.asarray(got), np.asarray(want)
        if got.shape != want.shape or not np.array_equal(got, want):
            raise AssertionError(
                f"pallas != jnp (shapes {got.shape} vs {want.shape}, "
                f"{int(np.sum(got != want)) if got.shape == want.shape else '?'}"
                f" elements differ)")

    checks = []

    def check(name):
        def deco(fn):
            checks.append((name, fn))
            return fn
        return deco

    s_north = size["north_shards"]
    for ops in (("&",), ("&", "-"), ("|", "&", "^")):
        @check(f"count_expr_stack[{s_north},{W}] {len(ops) + 1} operands "
               f"ops={''.join(ops)}")
        def _(ops=ops):
            planes = [bits(s_north, W) for _ in range(len(ops) + 1)]
            if ops == ("&",):   # through its public two-operand name
                got = int(pk.count_intersect_stack(*planes))
            else:
                got = int(pk.count_expr_stack(planes[0], planes[1:], ops))
            hi, lo = _count_expr_fn(ops, len(planes))(*planes)
            want = bitplane.combine_hi_lo(np.asarray(hi), np.asarray(lo))
            if got != int(want):
                raise AssertionError(f"pallas {got} != jnp {int(want)}")

    for nb in size["blocks"]:
        @check(f"count_blocks_stack[{nb},128]")
        def _(nb=nb):
            blocks = bits(nb, 128)
            same(pk.count_blocks_stack(blocks), jnp.sum(
                jax.lax.population_count(blocks).astype(jnp.int32)))

        @check(f"count_and_blocks_stack[{nb},128]")
        def _(nb=nb):
            a, b = bits(nb, 128), bits(nb, 128)
            same(pk.count_and_blocks_stack(a, b), jnp.sum(
                jax.lax.population_count(a & b).astype(jnp.int32)))

    for r in size["topn_rows"]:
        @check(f"topn_counts_stack[{r},{W}] k=5")
        def _(r=r):
            rows, filt = bits(r, W), bits(W)
            gv, gi = pk.topn_counts_stack(rows, filt, 5)
            wv, wi = bitplane._topn_counts_jnp(rows, filt, 5)
            same(gv, wv)
            same(gi, wi)

    s_mixed = size["mixed_shards"]
    r1, r2 = size["pairwise_rows"]
    for has_filt in (False, True):
        @check(f"pairwise_counts_stack[{r1}x{r2},{s_mixed},{W}] "
               f"filter={has_filt}")
        def _(has_filt=has_filt):
            a, b = bits(r1, s_mixed, W), bits(r2, s_mixed, W)
            filt = bits(s_mixed, W) if has_filt else None
            got = pk.pairwise_counts_stack(a, b, filt)
            fn = bitplane._pairwise_hi_lo_fn(has_filt)
            hi, lo = fn(a, b, filt) if has_filt else fn(a, b)
            same(np.asarray(got, np.int64),
                 bitplane.combine_hi_lo(np.asarray(hi), np.asarray(lo)))

    for depth in (16, 21):   # 21 pads to 24 sublanes
        for op, neg, allow_eq in (("lt", False, True), ("lt", True, False),
                                  ("gt", False, False), ("gt", True, True),
                                  ("eq", False, False), ("eq", True, False)):
            @check(f"bsi_range_mask {op} depth={depth} neg={neg} "
                   f"allow_eq={allow_eq}")
            def _(depth=depth, op=op, neg=neg, allow_eq=allow_eq):
                planes, sign, exists = bits(depth, W), bits(W), bits(W)
                pbits = jnp.asarray(bsi.predicate_bits(
                    (12345 * (depth + 1)) % (1 << depth), depth))
                got = pk.bsi_range_mask(op, planes, sign, exists, pbits,
                                        neg, allow_eq)
                if op == "eq":
                    want = bsi._range_eq_jnp(planes, sign, exists, pbits,
                                             neg)
                elif op == "lt":
                    want = bsi._range_lt_jnp(planes, sign, exists, pbits,
                                             neg, allow_eq)
                else:
                    want = bsi._range_gt_jnp(planes, sign, exists, pbits,
                                             neg, allow_eq)
                same(got, want)

    os.makedirs(LOG_DIR, exist_ok=True)
    for name in os.listdir(LOG_DIR):
        if name.startswith("kernel_"):
            os.unlink(os.path.join(LOG_DIR, name))
    failed = 0
    for i, (name, fn) in enumerate(checks):
        rec = {"kernel": name, "interpret": interpret, "ok": True}
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report every kernel
            failed += 1
            with open(os.path.join(LOG_DIR, f"kernel_{i:02d}.txt"),
                      "w") as f:
                f.write(name + "\n" + traceback.format_exc())
            first = str(e).strip().splitlines()[:6]
            rec.update(ok=False, error=f"{type(e).__name__}: "
                                       + " | ".join(first)[:900])
        print(json.dumps(rec), flush=True)
    return 1 if failed else 0


# -------------------------------------------------------------------- main


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="makes all data (default 0)")
    p.add_argument("--devices", type=int, default=None,
                   help="device count the server must report (fails on "
                        "another); default: accept what JAX reports")
    p.add_argument("--layout", choices=("single", "spmd"), default="single",
                   help="single: one server drives every local chip "
                        "(default); spmd: one --spmd server per chip")
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: dry run on the host at a tiny size with "
                        "interpret-mode kernels (tests); never a result")
    p.add_argument("--child", choices=("kernels",), default=None,
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "pilosa_tpu")):
        print("chip_smoke: pilosa_tpu/ is not next to this script — it "
              "smokes the repo it lives in", file=sys.stderr)
        return 2
    if args.child == "kernels":
        return child_kernels(args)

    sm = Smoke(args)
    # a killed smoke still stops every process it started (finally below)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        if args.layout == "single":
            phase_kernels(sm)   # the spmd layout asks one question only
        if phase_build(sm):
            try:
                if args.layout == "spmd":
                    phase_spmd(sm)
                else:
                    phase_serve(sm)
            except Exception as e:  # noqa: BLE001 — a phase died: report
                sm.fail(args.layout, f"{type(e).__name__}: {e}\n"
                        + traceback.format_exc(limit=4))
    finally:
        sm.stop_all()
        shutil.rmtree(DATA_DIR, ignore_errors=True)
    sm.say("summary", "set-up information (NOT metrics): "
           + json.dumps(sm.setup))
    sm.say("summary", "reduced: " + json.dumps(sm.reduced))
    sm.say("summary", f"wall {time.monotonic() - sm.t0:.0f} s of the "
                      f"{BUDGET_S} s budget")
    if sm.failures or sm.device is None:
        for f in sm.failures or ["no child reported a device"]:
            sm.say("summary", "FAILED " + f.replace("\n", "\n    "))
        return 1
    print(json.dumps({"ok": True, "device": sm.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: the driver thread beside the server.

The process's main thread runs the normal server (`pilosa_tpu.cli.main`);
this thread waits for it, loads the configuration's data over HTTP, warms
the cell's own query shapes, runs the load generator through the window,
compares every answer with the oracle, reads counters, spans and the
trace, and then interrupts the main thread so the server shuts down the
way an operator stops it.
"""

import http.client
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

from . import manifest, stats, traffic

HARNESS = os.path.dirname(os.path.abspath(__file__))
CHILD_TIMEOUT_S = 240       # any one helper process
WARM_SECONDS = 2.0          # load runs this long before the window opens
TRACE_SECONDS = 3.0


def say(msg):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


class Context:
    """What a metric reader may read (see benchmark/README.md)."""

    def __init__(self, run):
        self.cell = run.cell
        self.config = run.config
        self.traffic = run.spec
        self.device = run.device
        self.seconds = run.closed - run.opened
        self.setup_s = run.opened - run.t0
        self.records = run.window
        self.profiles = run.profiles
        self.before, self.after = run.before, run.after
        self.trace = run.trace
        self.traced_queries = run.traced_queries

    def latencies_ms(self, kind):
        return stats.latencies_ms(self.records, kind)

    def rate(self, kind):
        return stats.rate(self.records, kind, self.seconds)

    def delta(self, *path):
        """after - before of one number in the counter snapshots, e.g.
        delta("vars", "stacked", "hits"); None where it is absent."""
        a, b = self.after, self.before
        for key in path:
            if not isinstance(a, dict) or key not in a:
                return None
            a = a[key]
            b = b.get(key, {}) if isinstance(b, dict) else {}
        if not isinstance(a, (int, float)):
            return None
        return a - (b if isinstance(b, (int, float)) else 0)

    def timing(self, name, **tags):
        """(seconds, count) that the timing histograms `name{...}` whose
        tags include `tags` gained over the window, from /debug/vars."""
        seconds = count = 0
        for key in self.after.get("vars", {}).get("timings", {}):
            head, _, rest = key.partition("{")
            have = dict(t.split("=", 1) for t in rest.rstrip("}").split(",")
                        if "=" in t)
            if head != name or any(have.get(k) != v for k, v in tags.items()):
                continue
            seconds += self.delta("vars", "timings", key, "sum") or 0
            count += self.delta("vars", "timings", key, "count") or 0
        return seconds, count


class Run:
    def __init__(self, args, t0):
        self.args = args
        self.t0 = t0
        self.manifest = manifest.load()
        self.cell = manifest.cell(self.manifest, args.workload)
        self.config = manifest.config(self.manifest, self.cell["config"])
        self.rehearsal = args.rehearse_shards is not None
        if self.rehearsal:
            self.config["shards"] = args.rehearse_shards
        self.spec = traffic.load(manifest.traffic_path(self.cell["traffic"]))
        self.port = _free_port()
        self.data_dir = tempfile.mkdtemp(prefix="benchmark-data-")
        self.trace_dir = tempfile.mkdtemp(prefix="benchmark-trace-")
        self.children = []
        self.error = None
        self.result = None
        self.device = None
        self.trace = None
        self.traced_queries = []
        self.profiles = []
        self.acked_imports = 0

    # ---------------------------------------------------------- the server

    def server_argv(self):
        return ["server", "--bind", f"127.0.0.1:{self.port}",
                "--data-dir", self.data_dir, *self.config["server_flags"]]

    def thread(self):
        return threading.Thread(target=self._drive, name="benchmark-driver",
                                daemon=True)

    def _drive(self):
        try:
            self._run()
        except BaseException:  # noqa: BLE001 — reported by the main thread
            self.error = traceback.format_exc()
        finally:
            self.stop_children()
            os.kill(os.getpid(), signal.SIGINT)

    def cleanup(self):
        self.stop_children()
        for path in (self.data_dir, self.trace_dir):
            shutil.rmtree(path, ignore_errors=True)

    # ------------------------------------------------------------ children

    def _child(self, script, *argv):
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        proc = subprocess.Popen(
            [sys.executable, os.path.join(HARNESS, script), *argv],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=manifest.ROOT)
        self.children.append(proc)
        return proc

    def _call(self, what, **request):
        """Start `child.py <what>`; returns a function that waits for its
        answer."""
        proc = self._child("child.py", what)
        proc.stdin.write(json.dumps(
            dict(request, config=self.config, seed=self.args.seed)).encode())
        proc.stdin.close()

        def wait():
            try:
                out = proc.stdout.read()
                rc = proc.wait(timeout=CHILD_TIMEOUT_S)
            finally:
                if proc.poll() is None:
                    proc.kill()
            if rc != 0:
                raise RuntimeError(f"child.py {what} exited {rc}")
            return json.loads(out.splitlines()[-1])

        return wait

    def stop_children(self):
        for proc in self.children:
            if proc.poll() is None:
                proc.kill()
        for proc in self.children:
            proc.wait()
            for pipe in (proc.stdin, proc.stdout):
                if pipe and not pipe.closed:
                    pipe.close()

    # ---------------------------------------------------------------- HTTP

    def _http(self, method, path, body=None, ctype="text/plain"):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            conn.request(method, path, body, {"Content-Type": ctype})
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise RuntimeError(f"{method} {path}: HTTP {resp.status} "
                               f"{data[:200]!r}")
        return json.loads(data)

    def _wait_ready(self, limit=180):
        deadline = time.monotonic() + limit
        while True:
            try:
                return self._http("GET", "/status")
            except OSError:
                if time.monotonic() > deadline:
                    raise RuntimeError("server did not answer /status")
                time.sleep(0.1)

    def _snapshot(self):
        """The program's counters, from the endpoints the configuration
        names under `counters`."""
        return {name: self._http("GET", path)
                for name, path in self.config["counters"].items()}

    def _appends(self):
        """The counter that the configuration's durability guarantee says
        gains one with every acknowledged import (`append_counter`: a
        snapshot's name, then the keys down to the number)."""
        name, *keys = self.config["append_counter"]
        value = self._http("GET", self.config["counters"][name])
        for key in keys:
            value = value[key]
        return value

    # ------------------------------------------------------------- the run

    def _check_device(self):
        import jax

        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        if not self.rehearsal and self.device["platform"] == "cpu":
            raise RuntimeError("a measurement needs the chip; the host CPU "
                               "serves only --rehearse-shards")
        if self.device["count"] != self.cell["chips"]:
            raise RuntimeError(
                f"cell {self.cell['name']} is for {self.cell['chips']} "
                f"chip(s), JAX reports {self.device['count']}")

    def _warm(self, expected):
        """Every distinct query once (the first ones gather and upload the
        stacks and compile), and one operation of every other template,
        under a client number no load client has."""
        index = self.config["index"]
        wrong = 0
        for pql, want in sorted(expected.items()):
            got = self._http("POST", f"/index/{index}/query",
                             pql.encode())["results"][0]
            wrong += got != want
        draw = traffic.ClientDraw(self.spec, self.config, self.args.seed,
                                  self.spec["clients"])
        for op in draw.warm_ops():
            if op["kind"] == "query":
                continue
            write, readback = traffic.requests(op, index)
            self._http("POST", *write)
            self.acked_imports += 1
            got = self._http("POST", *readback)["results"][0]
            wrong += got != len(set(op["columns"]))
        return wrong

    def _start_loadgens(self):
        spec = self.spec
        n_proc = max(1, min(spec.get("processes", 1), spec["clients"]))
        per_client = math.ceil(spec["ops_per_client_per_s"] * (
            WARM_SECONDS + self.args.seconds + 10))
        procs = []
        for p in range(n_proc):
            proc = self._child("loadgen.py")
            plan = {"host": "127.0.0.1", "port": self.port,
                    "seed": self.args.seed, "config": self.config,
                    "traffic": spec, "profile": bool(self.args.trace),
                    "clients": list(range(p, spec["clients"], n_proc)),
                    "ops_per_client": per_client}
            proc.stdin.write(json.dumps(plan).encode() + b"\n")
            proc.stdin.flush()
            procs.append(proc)
        for proc in procs:
            if proc.stdout.readline().strip() != b"ready":
                raise RuntimeError("a load generator did not get ready")
        return procs

    def _tell(self, procs, word):
        for proc in procs:
            proc.stdin.write(word.encode() + b"\n")
            proc.stdin.flush()

    def _collect(self, procs):
        records, notes = [], {"cycled": 0, "errors": [], "hung": []}
        for proc in procs:
            out = json.loads(proc.stdout.readline())
            proc.wait(timeout=30)
            records.extend(out["records"])
            self.profiles.extend(out["profiles"])
            notes["cycled"] += out["cycled"]
            notes["errors"].extend(out["errors"])
            notes["hung"].extend(out["hung"])
        return records, notes

    def _trace_window(self, until):
        """Trace TRACE_SECONDS in the middle of what is left of the
        window; returns the traced span on the host's clock. The same
        span is marked in the trace, and the reduction clips the device's
        operations to the mark: busy time and the queries counted against
        it then cover one span, not the profiler's longer one."""
        import jax

        from . import reduce_trace

        length = min(TRACE_SECONDS, self.args.seconds / 3)
        time.sleep(max(0.0, (until - time.monotonic() - length) / 2))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(reduce_trace.SPAN_MARK):
            started = time.monotonic()
            time.sleep(length)
            stopped = time.monotonic()
        jax.profiler.stop_trace()
        return started, stopped

    def _memory_peak(self):
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return max(peaks)

    def _run(self):
        args = self.args

        def at(what):
            say(f"{what} at {time.monotonic() - self.t0:.1f} s")

        self._wait_ready()
        at("server up")
        self._check_device()
        appends_at_start = self._appends()
        pqls = traffic.distinct_queries(self.spec)
        oracle = self._call("expected", pqls=pqls)
        loaded = self._call(
            "load", url=f"http://127.0.0.1:{self.port}")()
        self.acked_imports += loaded["requests"]
        at(f"loaded {loaded}")
        procs = self._start_loadgens()
        expected = oracle()
        wrong_warm = self._warm(expected)
        at("warmed")
        self._tell(procs, "go")
        time.sleep(WARM_SECONDS)
        self.before = self._snapshot()
        self.opened = time.monotonic()
        until = self.opened + args.seconds
        traced = self._trace_window(until) if args.trace else None
        time.sleep(max(0.0, until - time.monotonic()))
        self.closed = time.monotonic()
        self.after = self._snapshot()
        memory_peak = self._memory_peak()
        self._tell(procs, "stop")
        records, notes = self._collect(procs)
        # one more pass once the load has stopped: stacks that writes
        # patched all through the window must still answer exactly
        wrong_after = self._warm(expected) if any(
            op["kind"] != "query" for op in self.spec["operations"]) else 0
        # every import acknowledged since the server came up (the load,
        # the warm-ups, every client's from `go` to `stop`) against the
        # appends the log gained
        self.acked_imports += sum(
            1 for r in records if r[stats.STATUS] == 200
            and r[stats.KIND] not in ("query", "readback"))
        appended = self._appends() - appends_at_start

        for r in records:
            if r[stats.KIND] == "query":
                r[stats.EXPECT] = expected[r[stats.PQL]]
        self.window = stats.in_window(records, self.opened, self.closed)
        said_wrong, unanswered = stats.wrong(records)
        compared = {
            "nothing_answered": [int(not any(
                stats.ok(r) for r in self.window)), 0],
            "wrong_answers": [said_wrong + wrong_warm + wrong_after, 0],
            "unanswered": [unanswered + len(notes["hung"]), 0],
            "load_bits_missing": [
                loaded["bits_sent"] - loaded["bits_acknowledged"], 0],
            "acked_not_appended": [self.acked_imports - appended, 0],
        }
        summary = stats.summary(records, self.opened, self.closed)
        if args.keep_records:
            _keep_records(args.keep_records, records, self.opened,
                          self.closed)
        if traced:
            from . import reduce_trace

            self.traced_queries = [
                r[stats.PQL] for r in stats.in_window(records, *traced)
                if r[stats.KIND] == "query" and stats.ok(r)]
            xplane = reduce_trace.find_xplane(self.trace_dir)
            events = reduce_trace.extract(xplane)
            if args.keep_trace:
                _keep_trace(args.keep_trace, xplane, events)
            self.trace = reduce_trace.reduce(events)
            if self.trace is None:
                raise RuntimeError("no operation ran on a device in the "
                                   "traced span")
        ctx = Context(self)
        read = {}
        for group in ("end_to_end", "per_layer"):
            read[group] = {}
            for entry in manifest.metrics(self.manifest, group,
                                          self.cell["name"]):
                value = manifest.reader(entry["name"])(ctx)
                if value is not None:
                    read[group][entry["name"]] = {"value": value,
                                                  "unit": entry["unit"]}
        # the line carries one group; the other is said on stderr only
        # (end-to-end numbers of a traced run are slowed by the tracing)
        line, aside = ("per_layer", "end_to_end") if args.trace else (
            "end_to_end", "per_layer")
        metrics = read[line]
        say(f"{aside} (not in the line): " + json.dumps(
            sorted(read[aside]) if self.rehearsal else
            {k: v["value"] for k, v in read[aside].items()}))
        device = dict(self.device, memory_peak_bytes=memory_peak)
        result = {
            "correct": all(v <= limit for v, limit in compared.values()),
            "attempted": summary["attempted"],
            "failed": summary["failed"],
            "metrics": metrics,
            "device": device,
        }
        if self.trace:
            device["busy_s"] = self.trace["busy_s"]
            device["window_s"] = self.trace["window_s"]
            device["idle_share_by_device"] = self.trace[
                "idle_share_by_device"]
            result["breakdown"] = {"device_ops": self.trace["device_ops"],
                                   "idle_gaps": self.trace["idle_gaps"]}
        result["window"] = dict(summary, notes=notes)
        if args.fault:
            result["fault"] = args.fault
        result["compared"] = {k: {"value": v, "limit": limit}
                              for k, (v, limit) in compared.items()}
        self.result = result


def _keep_records(path, records, opened, closed):
    import gzip

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"opened": opened, "closed": closed,
                   "fields": stats.RECORD, "records": records}, f)


def _keep_trace(path, xplane, events):
    """--keep-trace: the extracted events (gzipped JSON) and a listing of
    the trace's planes and lines, for a look by hand and for the recording
    the tests reduce."""
    import gzip

    from . import reduce_trace

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with gzip.open(path + ".events.json.gz", "wt") as f:
        json.dump(events, f)
    with open(path + ".planes.txt", "w") as f:
        f.write(reduce_trace.describe(xplane))


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]

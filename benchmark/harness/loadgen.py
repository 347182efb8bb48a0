"""Closed-loop load generator. Standard library only: it runs as child
processes of `run.py` (started with subprocess, never forked), so that the
generator's interpreter lock is not the server's.

    python benchmark/harness/loadgen.py < plan.json

Protocol on the pipes: the parent writes the plan (one JSON line) on
stdin; the child draws every client's operations, opens the connections,
prints `ready`; the parent writes `go`; every client thread sends, waits,
records, repeats; the parent writes `stop`; every client finishes the
operation it has in flight (an acknowledged write is still read back),
and the child prints one JSON line of records and exits.

Times are `time.monotonic()`, which on Linux is one clock for every process
of the host, so the parent cuts its window out of the records itself.

A record is [client, kind, sent, done, status, answer, gap, expect, pql] where
kind is "query", "import_bits" or "readback", status the HTTP status (0: no
reply), answer the count a query returned, gap the seconds between the
client's previous reply and this send (`gen.turnaround_ms`), and expect,
for a read-back, how many distinct columns of that row this client has had
acknowledged: what the read-back must return. The parent fills in what a
query must return from the oracle, by its `pql` text.
"""

import http.client
import json
import os
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic  # noqa: E402

REQUEST_TIMEOUT_S = 120


def span_table(node, out=None):
    """A `?profile=true` span tree -> {span name: [seconds, self seconds]},
    self being a span's time less its direct children's."""
    if out is None:
        out = {}
    children = node.get("children") or []
    row = out.setdefault(node["name"], [0.0, 0.0])
    row[0] += node["duration"]
    row[1] += node["duration"] - sum(c["duration"] for c in children)
    for child in children:
        span_table(child, out)
    return out


class Client(threading.Thread):
    def __init__(self, plan, number, stop):
        super().__init__(daemon=True, name=f"client-{number}")
        self.plan = plan
        self.number = number
        self.stop = stop
        self.go = threading.Event()
        self.records = []
        self.profiles = []
        self.cycled = 0
        self.error = None
        draw = traffic.ClientDraw(plan["traffic"], plan["config"],
                                  plan["seed"], number)
        self.ops = [self._prepare(draw.draw())
                    for _ in range(plan["ops_per_client"])]
        self.acked = {}         # (field, row) -> set of acknowledged columns
        self._connect()

    def _connect(self):
        self.conn = http.client.HTTPConnection(
            self.plan["host"], self.plan["port"], timeout=REQUEST_TIMEOUT_S)
        self.conn.connect()

    def _prepare(self, op):
        """(operation, its HTTP requests), built before anything is timed."""
        return op, traffic.requests(op, self.plan["config"]["index"],
                                    profile=self.plan.get("profile"))

    def _send(self, path, body, ctype):
        """-> (status, parsed JSON or None, sent, done)"""
        sent = time.monotonic()
        try:
            self.conn.request("POST", path, body,
                              {"Content-Type": ctype})
            # The server writes a reply's headers and body as two segments
            # with Nagle on, so on a host whose kernel delays acks a
            # kept-alive client would time its own 40 ms ack timer, not the
            # serving path. Linux leaves quick-ack mode by itself: ask again
            # for every reply.
            self.conn.sock.setsockopt(
                socket.IPPROTO_TCP, socket.TCP_QUICKACK, 1)
            resp = self.conn.getresponse()
            data = resp.read()
            done = time.monotonic()
            status = resp.status
        except (OSError, http.client.HTTPException) as e:
            done = time.monotonic()
            self.error = f"{type(e).__name__}: {e}"
            self.conn.close()
            try:
                self._connect()
            except OSError:
                pass        # the next send reports it
            return 0, None, sent, done
        try:
            return status, json.loads(data), sent, done
        except ValueError:
            return status, None, sent, done

    def run(self):
        self.go.wait()
        last_done = time.monotonic()
        i = 0
        n = len(self.ops)
        while not self.stop.is_set():
            if i == n:
                i = 0
                self.cycled += 1
            op, sends = self.ops[i]
            i += 1
            status, reply, sent, done = self._send(*sends[0])
            gap = sent - last_done
            last_done = done
            if op["kind"] == "query":
                answer = None
                if status == 200 and reply is not None:
                    answer = (reply.get("results") or [None])[0]
                    if "profile" in reply and len(self.profiles) < 4096:
                        self.profiles.append(
                            span_table(reply["profile"]["spans"]))
                self.records.append(
                    [self.number, "query", sent, done, status, answer, gap,
                     None, op["pql"]])
                continue
            have = self.acked.setdefault((op["field"], op["row"]), set())
            if status == 200:
                have.update(op["columns"])
            self.records.append(
                [self.number, op["kind"], sent, done, status, None, gap,
                 None, None])
            if status != 200:
                continue
            status, reply, sent, done = self._send(*sends[1])
            answer = None
            if status == 200 and reply is not None:
                answer = (reply.get("results") or [None])[0]
            self.records.append(
                [self.number, "readback", sent, done, status, answer,
                 0.0, len(have), op["readback"]])
            last_done = done
        self.conn.close()


def main():
    plan = json.loads(sys.stdin.readline())
    stop = threading.Event()
    clients = [Client(plan, number, stop) for number in plan["clients"]]
    for c in clients:
        c.start()
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        raise SystemExit("loadgen: expected 'go'")
    for c in clients:
        c.go.set()
    sys.stdin.readline()            # "stop" (or the parent went away)
    stop.set()
    for c in clients:
        c.join(timeout=REQUEST_TIMEOUT_S + 10)
    out = {
        "records": [r for c in clients for r in c.records],
        "profiles": [p for c in clients for p in c.profiles],
        "cycled": sum(c.cycled for c in clients),
        "errors": [c.error for c in clients if c.error],
        "hung": [c.number for c in clients if c.is_alive()],
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

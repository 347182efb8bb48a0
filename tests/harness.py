"""In-process server harness (reference: test/pilosa.go MustRunCluster —
boots real servers on ephemeral ports)."""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from pilosa_tpu.core import Holder
from pilosa_tpu.server import API, Client, PilosaHTTPServer


class ServerHarness:
    """One in-process node: holder + API + HTTP on an ephemeral port."""

    def __init__(self, data_dir=None, **api_kwargs):
        self.data_dir = data_dir or tempfile.mkdtemp(prefix="pilosa_tpu_test_")
        self.holder = Holder(self.data_dir, use_snapshot_queue=False).open()
        self._api_kwargs = api_kwargs
        self.api = API(self.holder, **api_kwargs)
        self.server = PilosaHTTPServer(self.api, host="127.0.0.1", port=0)
        self.server.start()
        self.client = Client(self.server.address)

    @property
    def address(self):
        return self.server.address

    def reopen(self):
        """Restart from disk (reference: test/Command.Reopen)."""
        self.server.stop()
        self.holder.reopen()
        self.api = API(self.holder, **self._api_kwargs)
        self.server = PilosaHTTPServer(self.api, host="127.0.0.1", port=0)
        self.server.start()
        self.client = Client(self.server.address)

    def close(self):
        self.server.stop()
        self.api.close()
        self.holder.close()


def load_segment_index(h, segment, cfg, seed):
    """Create the benchmark configuration `cfg`'s index on harness `h` and
    send every fragment as the benchmark's loader sends it: a roaring blob
    a field and shard (array containers) from `segment.shard_planes`.
    Returns (bits sent, bits `import_roaring` acknowledged)."""
    import numpy as np

    from pilosa_tpu.roaring import Bitmap, serialize

    h.client.create_index(cfg["index"])
    for field in cfg["fields"]:
        h.client.create_field(cfg["index"], field)
    per_row = cfg["shard_width"] // (1 << 16)
    sent = acknowledged = 0
    for shard in range(cfg["shards"]):
        planes = segment.shard_planes(cfg, seed, shard)
        for field in cfg["fields"]:
            bitmap = Bitmap()
            for row in cfg["rows"]:
                bitmap.replace_dense_words(row * per_row, per_row,
                                           planes[field, row])
                sent += int(np.bitwise_count(planes[field, row]).sum())
            acknowledged += h.client.import_roaring(
                cfg["index"], field, shard,
                serialize(bitmap, optimize=False))["changed"]
    return sent, acknowledged


class ClusterHarness:
    """n in-process nodes with a shared static topology (reference:
    test.MustRunCluster test/pilosa.go:390 — real servers, real HTTP,
    ephemeral ports; ModHasher optionally for deterministic placement)."""

    def __init__(self, n, replica_n=1, hasher=None, api_kwargs=None):
        from pilosa_tpu.cluster import Cluster, Node

        # phase 1: boot servers (cluster-less) to learn ephemeral ports
        self.nodes = [ServerHarness() for _ in range(n)]
        node_list = [
            Node(id=h.address.split("//", 1)[1], uri=h.address)
            for h in self.nodes
        ]
        # phase 2: attach cluster-aware APIs now that all URIs are known
        for h in self.nodes:
            local_id = h.address.split("//", 1)[1]
            cluster = Cluster(
                nodes=[Node(n_.id, n_.uri) for n_ in node_list],
                local_id=local_id, replica_n=replica_n, hasher=hasher,
                path=h.data_dir)
            h.api = API(h.holder, cluster=cluster, client_factory=Client,
                        **(api_kwargs or {}))
            h.server.api = h.api
            h.cluster = h.api.cluster

    def __getitem__(self, i):
        return self.nodes[i]

    def __len__(self):
        return len(self.nodes)

    def owner_of(self, index, shard):
        """The harness node that is primary owner of (index, shard)."""
        primary = self.nodes[0].cluster.shard_nodes(index, shard)[0]
        return self.node_by_id(primary.id)

    def non_owner_of(self, index, shard):
        owners = {n.id for n in
                  self.nodes[0].cluster.shard_nodes(index, shard)}
        for h in self.nodes:
            if h.cluster.local_id not in owners:
                return h
        return None

    def node_by_id(self, node_id):
        for h in self.nodes:
            if h.cluster.local_id == node_id:
                return h
        return None

    def close(self):
        for h in self.nodes:
            h.close()


class SpmdMeshCluster:
    """2 real server processes forming a gloo-backed global CPU mesh
    (--spmd-serve on --spmd-cpu-collectives gloo). Unlike the bare
    --spmd harness (tests/test_spmd.py), gloo gives the CPU backend REAL
    cross-process collectives, so the mesh-resident serving plane forms
    even on single-chip CI hosts: 2 virtual devices per process -> a
    4-device mesh whose psum actually crosses the process boundary.

    Used by tests/test_spmd_mesh.py (same-cluster A/B via the runtime
    POST /debug/spmd switch)."""

    def __init__(self, n=2, serve_mode="on", extra_flags=()):
        ports = _free_ports(n + 1)
        self.ports, spmd_port = ports[:n], ports[n]
        hosts = ",".join(f"127.0.0.1:{p}" for p in self.ports)
        self.dirs = [tempfile.mkdtemp(prefix="pilosa-mesh-")
                     for _ in range(n)]
        self.procs = []
        self.logs = []
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        flags = ["--spmd", "--spmd-port", str(spmd_port),
                 "--spmd-serve", serve_mode,
                 "--spmd-cpu-collectives", "gloo",
                 "--fusion", "on",
                 *extra_flags]
        for i, port in enumerate(self.ports):
            log = open(os.path.join(self.dirs[i], "server.log"), "w")
            self.logs.append(log)
            self.procs.append(subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "--bind", f"127.0.0.1:{port}",
                 "--data-dir", self.dirs[i],
                 "--cluster-hosts", hosts,
                 "--replicas", "1"] + flags,
                stdout=log, stderr=subprocess.STDOUT, env=env,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))))
        self.clients = [Client(f"http://127.0.0.1:{p}", timeout=120)
                        for p in self.ports]
        # the cluster sorts nodes by id: the coordinator (step initiator)
        # is the lexically-smallest host:port
        self.coord = min(range(n),
                         key=lambda i: f"127.0.0.1:{self.ports[i]}")

    def wait_ready(self, timeout=240):
        deadline = time.time() + timeout
        pending = set(range(len(self.procs)))
        while pending and time.time() < deadline:
            for i in list(pending):
                if self.procs[i].poll() is not None:
                    raise RuntimeError(
                        f"node {i} exited: " + self.tail(i))
                try:
                    self.clients[i]._request("GET", "/status")
                    pending.discard(i)
                except Exception:
                    pass
            time.sleep(0.5)
        if pending:
            raise TimeoutError(
                f"nodes {sorted(pending)} not ready: "
                + "; ".join(self.tail(i) for i in pending))

    def set_mode(self, mode):
        """Runtime serve-mode switch on EVERY node (POST /debug/spmd)."""
        for cl in self.clients:
            cl._request("POST", "/debug/spmd",
                        body=json.dumps({"serve_mode": mode}).encode())

    def debug(self, i):
        return self.clients[i]._request("GET", "/debug/spmd")

    def stats(self, i):
        return self.clients[i]._request("GET", "/internal/spmd/stats")

    def tail(self, i, n=2000):
        self.logs[i].flush()
        with open(self.logs[i].name) as f:
            return f.read()[-n:]

    def close(self):
        for p in self.procs:
            try:
                p.terminate()
            except OSError:
                pass
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self.logs:
            log.close()
        import shutil

        for d in self.dirs:
            shutil.rmtree(d, ignore_errors=True)


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports

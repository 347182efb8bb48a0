"""Golden PQL suite on REAL multi-process clusters — the BASELINE.md
config-5 analog (the reference's 4-node full-suite benchmark runs its
black-box executor suite against a live cluster; real multi-chip isn't
available here, so this is the CPU-cluster equivalent).

Cases live in tests/testdata/golden_pql.json (~35 ported from
/root/reference/executor_test.go's 4,138-LoC black-box suite), with
column placeholders "@S+OFF" resolved to S*SHARD_WIDTH+OFF so the
dataset spans 4 shards at any shard-width exponent.

Two transports, matching BASELINE config 5's two query planes:
- plain HTTP cluster (3 nodes, replicas=2), queries spread across ALL
  nodes — any-node answers must agree;
- --spmd cluster (3 processes, global 6-device gloo mesh), queries via
  coordinator AND non-coordinator (collective data plane underneath).
"""

import json
import pathlib

import pytest

from pilosa_tpu.shardwidth import SHARD_WIDTH

from .test_clusterproc import ProcCluster
from .test_spmd import SpmdCluster

GOLDEN = pathlib.Path(__file__).parent / "testdata" / "golden_pql.json"


def _resolve(obj):
    """Recursively substitute "@S+OFF" placeholders with real columns."""
    if isinstance(obj, str) and obj.startswith("@"):
        shard, off = obj[1:].split("+")
        return int(shard) * SHARD_WIDTH + int(off)
    if isinstance(obj, list):
        return [_resolve(x) for x in obj]
    if isinstance(obj, dict):
        return {k: _resolve(v) for k, v in obj.items()}
    return obj


def _resolve_pql(pql):
    import re

    return re.sub(
        r"@(\d+)\+(\d+)",
        lambda m: str(int(m.group(1)) * SHARD_WIDTH + int(m.group(2))),
        pql)


def load_golden():
    doc = json.loads(GOLDEN.read_text())
    setup = [_resolve_pql(s) for s in doc["setup"]]
    cases = []
    for c in doc["cases"]:
        # "want" checks results[0]; "want_all" the full results list
        # (multi-call queries like "Store(...) Row(...)")
        if "want_all" in c:
            want, whole = _resolve(c["want_all"]), True
        else:
            want, whole = _resolve(c["want"]), False
        cases.append((c["name"], _resolve_pql(c["query"]), want, whole))
    return setup, cases


def _create_schema(client):
    client.create_index("gold")
    client.create_field("gold", "f", {"type": "set"})
    client.create_field("gold", "g", {"type": "set"})
    client.create_field("gold", "m", {"type": "mutex"})
    client.create_field("gold", "b", {"type": "bool"})
    client.create_field("gold", "v",
                        {"type": "int", "min": -100, "max": 1000})
    client.create_field("gold", "t",
                        {"type": "time", "timeQuantum": "YMD"})
    client.create_field("gold", "kf", {"type": "set", "keys": True})
    client.create_field("gold", "w", {"type": "set"})


def _apply_setup(client, setup):
    # one call per write: writes route/fan out individually, like a real
    # client stream (reference: executor_test.go drives Set one by one)
    for pql in setup:
        res = client.query("gold", pql)
        assert "error" not in res, f"{pql}: {res}"


def _run_cases(clients, cases):
    failures = []
    for i, (name, pql, want, whole) in enumerate(cases):
        client = clients[i % len(clients)]  # spread across nodes
        results = client.query("gold", pql)["results"]
        got = results if whole else results[0]
        if got != want:
            failures.append(f"{name} (via node {i % len(clients)}): "
                            f"{pql}\n  got:  {got}\n  want: {want}")
    assert not failures, "\n".join(failures)


@pytest.fixture(scope="module")
def http_cluster():
    import time

    c = ProcCluster(3, replicas=2)
    try:
        c.wait_ready()
        setup, _ = load_golden()
        _create_schema(c.clients[0])
        time.sleep(1.0)  # DDL broadcast settles
        _apply_setup(c.clients[0], setup)
        yield c
    finally:
        c.close()


@pytest.fixture(scope="module")
def spmd_cluster():
    import time

    c = SpmdCluster(3)
    c.coord = min(range(3), key=lambda i: f"127.0.0.1:{c.ports[i]}")
    try:
        c.wait_ready()
        setup, _ = load_golden()
        _create_schema(c.clients[c.coord])
        time.sleep(1.0)
        _apply_setup(c.clients[c.coord], setup)
        yield c
    finally:
        c.close()


def test_golden_over_http_cluster(http_cluster):
    _, cases = load_golden()
    _run_cases(http_cluster.clients, cases)


def test_golden_over_spmd_cluster(spmd_cluster):
    _, cases = load_golden()
    c = spmd_cluster
    # coordinator first, then a non-coordinator (any-node initiation)
    non_coord = next(i for i in range(3) if i != c.coord)
    _run_cases([c.clients[c.coord], c.clients[non_coord]], cases)

"""Mesh-resident SPMD serving (--spmd-serve) — single-process units.

The 2-process gloo differential lives in tests/test_spmd_mesh.py (slow);
everything here is the fast half of the contract: serve-mode plumbing,
the mesh stack cache's keying/generation/shadow semantics, the batched
collective program vs serial counts, and the /debug/spmd surface on a
no-spmd node.
"""

import os
import threading
from collections import OrderedDict

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from pilosa_tpu.cluster.meshstacks import (  # noqa: E402
    MeshStackCache,
    entry_key,
    leaf_views,
)
from pilosa_tpu.cluster.spmd import (  # noqa: E402
    SpmdDataPlane,
    SpmdError,
)
from pilosa_tpu.core.view import (  # noqa: E402
    VIEW_BSI_GROUP_PREFIX,
    VIEW_STANDARD,
)
from pilosa_tpu.shardwidth import WORDS_PER_ROW  # noqa: E402

from .harness import ServerHarness  # noqa: E402


def _plane(serve_mode="off"):
    return SpmdDataPlane(None, None, None, serve_mode=serve_mode)


# -- serve-mode plumbing ------------------------------------------------------


def test_serve_mode_default_and_coercion():
    assert _plane().serve_mode == "off"
    assert _plane("on").serve_mode == "on"
    assert _plane("shadow").serve_mode == "shadow"
    # an unknown boot value degrades to the safe default, never raises
    assert _plane("sideways").serve_mode == "off"


def test_set_serve_mode_runtime_switch():
    p = _plane()
    assert p.set_serve_mode("on") == "on"
    assert p.serve_mode == "on"
    assert p.set_serve_mode("http") == "http"
    with pytest.raises(SpmdError):
        p.set_serve_mode("sideways")
    assert p.serve_mode == "http"  # failed switch leaves the mode alone


def test_http_mode_forces_decline():
    """serve_mode=http declines before touching call/cluster state: the
    same cluster can A/B the HTTP fan-out against the collective."""
    p = _plane("http")
    assert p.maybe_execute(None, None, []) == (False, None)


def test_debug_snapshot_shape():
    snap = _plane("on").debug_snapshot()
    assert snap["serve_mode"] == "on"
    assert snap["steps"]["announced"] == 0
    assert snap["steps"]["entered"] == 0
    assert snap["steps"]["exited"] == 0
    assert snap["stream"]["errors"] == 0
    assert snap["queries"]["fused"] == 0
    assert snap["mesh_cache"]["entries"] == 0
    assert "http_data_plane_bytes" in snap


# -- mesh stack cache ---------------------------------------------------------


def test_entry_key_and_leaf_views():
    assert entry_key(["row", "f", 7]) == ("row", "f", 7)
    assert entry_key(["bsicond", "v", ">", [10]]) \
        == ("bsicond", "v", ">", (10,))
    # single-threshold conditions ship a scalar on the wire
    assert entry_key(["bsicond", "v", ">", 0]) == ("bsicond", "v", ">", 0)
    assert entry_key(["timerow", "t", 1, ["std_2019", "std_2020"]]) \
        == ("timerow", "t", 1, ("std_2019", "std_2020"))
    assert leaf_views(["row", "f", 7]) == ("f", (VIEW_STANDARD,))
    assert leaf_views(["bsicond", "v", ">", [10]]) \
        == ("v", (VIEW_BSI_GROUP_PREFIX + "v",))
    assert leaf_views(["timerow", "t", 1, ["a", "b"]]) == ("t", ("a", "b"))


def _block(fill=0):
    b = np.zeros((2, WORDS_PER_ROW), dtype=np.uint32)
    if fill:
        b[0, 0] = fill
    return b


def _key(index="i", field="f", row=1, seg_len=2, shards=(0, 1)):
    return (index, ("row", field, row), seg_len, tuple(shards))


def test_mesh_cache_hit_requires_matching_gens():
    c = MeshStackCache()
    key, gens = _key(), ((1, 1), (2, 1))
    arr = object()  # the cache stores the global-array HANDLE opaquely
    assert c.get(key, gens) is None
    c.put(key, gens, arr, _block(3))
    assert c.get(key, gens) is arr
    # a local write bumps a fragment generation -> entry invalidated
    assert c.get(key, ((1, 2), (2, 1))) is None
    s = c.stats()
    assert s["hits"] == 1 and s["misses"] == 2
    assert s["invalidations"] == 1
    assert s["entries"] == 0 and s["bytes"] == 0


def test_mesh_cache_lru_eviction_and_ledger():
    nbytes = _block().size * 4
    c = MeshStackCache(max_bytes=nbytes)  # budget holds exactly one block
    g = ((1, 1),)
    c.put(_key(row=1), g, object(), _block(1))
    c.put(_key(row=2), g, object(), _block(2))
    assert c.evictions == 1
    assert c.get(_key(row=1), g) is None  # LRU victim
    s = c.stats()
    assert s["entries"] == 1 and s["bytes"] == nbytes
    # the HBM ledger tracks the surviving entry only, pool-tagged by repr
    assert sum(e["bytes"] for e in s["ledger"]) == nbytes
    assert all(e["index"] == "i" and e["field"] == "f"
               for e in s["ledger"])


def test_mesh_cache_shadow_probe_digest():
    c = MeshStackCache()
    key, gens = _key(), ((1, 1),)
    c.shadow_probe(key, gens, _block(5))  # miss: parks digest, no bytes
    assert c.stats()["bytes"] == 0
    c.shadow_probe(key, gens, _block(5))  # same content -> clean hit
    c.shadow_probe(key, gens, _block(6))  # same gens, new content!
    s = c.stats()["shadow"]
    assert s == {"probes": 3, "hits": 2, "mismatches": 1}
    # a shadow-parked (array-less) entry never serves on the hot path
    assert c.get(key, gens) is None


def test_mesh_cache_invalidate_index():
    c = MeshStackCache()
    g = ((1, 1),)
    c.put(_key(index="a"), g, object(), _block(1))
    c.put(_key(index="b"), g, object(), _block(2))
    c.invalidate_index("a")
    assert c.get(_key(index="a"), g) is None
    assert c.get(_key(index="b"), g) is not None
    assert c.stats()["entries"] == 1


# -- batched collective program ----------------------------------------------


def _np_eval(sig, stacks):
    if sig[0] == "leaf":
        return stacks[sig[1]]
    op, subs = sig
    acc = _np_eval(subs[0], stacks)
    for s in subs[1:]:
        p = _np_eval(s, stacks)
        acc = {"&": acc & p, "|": acc | p, "^": acc ^ p,
               "&~": acc & ~p}[op]
    return acc


def _popcount(arr):
    return int(np.unpackbits(arr.view(np.uint8)).sum())


def test_count_batch_fn_matches_serial_counts():
    """K trees, one program: mixed signatures AND the vmapped
    identical-run path (bucket padding repeats plans[0]) both produce
    the serial per-tree popcounts, in plan order."""
    rng = np.random.default_rng(7)
    a, b = (rng.integers(0, 2**32, size=(4, WORDS_PER_ROW),
                         dtype=np.uint32) for _ in range(2))
    leaf = ("leaf", 0)
    inter = ("&", (("leaf", 0), ("leaf", 1)))
    sigs = (leaf, inter, leaf, leaf)      # trailing run -> vmapped group
    arities = (1, 2, 1, 1)
    stacks = [a, a, b, a, a]
    p = _plane("on")
    hilo = np.asarray(p._count_batch_fn(sigs, arities)(*stacks))
    assert hilo.shape == (2, len(sigs))  # one fetch for the whole batch
    got = [(int(h) << 16) + int(l) for h, l in zip(hilo[0], hilo[1])]
    want = [_popcount(_np_eval(s, stacks[o:o + n]))
            for s, o, n in zip(sigs, (0, 1, 3, 4), arities)]
    assert got == want
    # same (sigs, arities) -> the jitted program is reused, not rebuilt
    assert len(p._fns) == 1
    p._count_batch_fn(sigs, arities)
    assert len(p._fns) == 1


# -- EXPLAIN annotations ------------------------------------------------------


def test_plan_node_and_psum_bytes():
    from pilosa_tpu.pql import parse

    call = parse("Count(Row(f=1))").calls[0]
    node = _plane("on").plan_node(None, call, [0, 1, 2])
    assert node["strategy"] == "spmd-collective"
    ann = node["annotations"]
    assert ann["spmd"] is True
    assert ann["dispatches"] == 0  # zero per-node fan-out dispatches
    assert ann["shards"] == 3
    assert len(ann["mesh"]) == 2
    assert SpmdDataPlane._psum_bytes("count", 5) == 8
    assert SpmdDataPlane._psum_bytes("topn", [1, 2, 3]) == 24


def test_plan_eligible_gated_on_serve_mode():
    from pilosa_tpu.pql import parse

    call = parse("Count(Row(f=1))").calls[0]
    assert not _plane("off").plan_eligible(None, call)
    assert not _plane("http").plan_eligible(None, call)
    # serve=on with no cluster still declines (no mesh to serve from)
    assert not _plane("on").plan_eligible(None, call)


# -- fusion ledger: mesh programs --------------------------------------------


def test_fusion_mesh_program_key_and_touch():
    from pilosa_tpu.exec import fusion

    sigs = (("leaf", 0),)
    key = fusion.mesh_program_key("fp1", sigs, 4, [2, 1])
    assert key == ("fp1", sigs, 4, (2, 1))

    class _Ev:
        _lock = threading.Lock()
        _fns = OrderedDict()

    ev = _Ev()
    ev._fns[("count_batch", sigs, (1,))] = object()
    assert not fusion.touch_mesh_program(
        key, ev, ("count_batch", sigs, (1,)), compile_ms=12.0)
    assert fusion.touch_mesh_program(  # second touch = program-cache hit
        key, ev, ("count_batch", sigs, (1,)))
    entries = [e for e in fusion.snapshot()["programs"]
               if e["fingerprint"] == "fp1"]
    assert entries and entries[0]["mesh"] == [2, 1]
    assert entries[0]["hits"] == 2


# -- /debug/spmd on a no-spmd node -------------------------------------------


def test_debug_spmd_disabled_node():
    h = ServerHarness()
    try:
        assert h.client._request("GET", "/debug/spmd") \
            == {"enabled": False}
        from pilosa_tpu.server import ClientError

        import json

        with pytest.raises(ClientError):
            h.client._request("POST", "/debug/spmd",
                              body=json.dumps(
                                  {"serve_mode": "on"}).encode())
    finally:
        h.close()

"""POST /index/{i}/query-batch: several queries in one request, each
executed as POST /index/{i}/query would execute it.

The contract under test: every slot answers exactly what `query`
answers, for every read family and for mixed traffic; one bad member
reports its own error and never sinks the others; the route compiles no
program that `query` would not; and SLOW QUERY lines carry the batch=
size that `GroupCommit` stamps.
"""

import datetime as dt
import json
import re

import numpy as np
import pytest

from pilosa_tpu.core import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.server.api import API
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.logger import CaptureLogger

from .harness import ServerHarness

N_SHARDS = 3
N_ROWS = 6


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One holder and one default API over it: two set fields and a time
    field, random bits over three shards."""
    tmp = tmp_path_factory.mktemp("query_batch")
    holder = Holder(str(tmp)).open()
    api = API(holder)
    api.create_index("i")
    api.create_field("i", "f")
    api.create_field("i", "g")
    api.create_field("i", "t", FieldOptions.time_field("YMD"))
    rng = np.random.default_rng(17)
    # a pool of 300 columns a shard, so that two rows of two fields meet
    pool = np.concatenate([s * SHARD_WIDTH + np.arange(300)
                           for s in range(N_SHARDS)])
    for fld in ("f", "g"):
        cols = rng.choice(pool, size=600, replace=False)
        rows = rng.integers(0, N_ROWS, size=600)
        api.import_bits("i", fld, rows.tolist(), cols.tolist())
    cols = rng.choice(pool, size=200, replace=False)
    rows = rng.integers(0, N_ROWS, size=200)
    stamps = [dt.datetime(2019, 1, 1) + dt.timedelta(days=int(d))
              for d in rng.integers(0, 400, size=200)]
    api.import_bits("i", "t", rows.tolist(), cols.tolist(),
                    timestamps=stamps)
    yield holder, api
    holder.close()


def _same_result(a, b):
    if hasattr(a, "segments") or hasattr(b, "segments"):
        return np.array_equal(a.columns(), b.columns())
    return a == b


# ------------------------------------------------- every family, slot == query


FAMILIES = {
    "Count": "Count(Intersect(Row(f={a}), Row(g={b})))",
    "Row": "Row(g={a})",
    "Range": "Range(t={a}, from=2019-02-01T00:00, to=2019-09-01T00:00)",
    "Intersect": "Intersect(Row(f={a}), Row(g={b}))",
    "Union": "Union(Row(f={a}), Row(g={b}))",
    "Difference": "Difference(Row(f={a}), Row(g={b}))",
    "Xor": "Xor(Row(f={a}), Row(g={b}))",
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_query_batch_answers_what_query_answers(env, family):
    """Seventeen members of one family (past the deleted path's bucket
    of 16), one of them over a row that holds nothing: member by member
    what `query` answers, and no slot an error."""
    holder, api = env
    rng = np.random.default_rng(5)
    batch = [FAMILIES[family].format(a=rng.integers(0, N_ROWS),
                                     b=rng.integers(0, N_ROWS))
             for _ in range(16)]
    batch.append(FAMILIES[family].format(a=997, b=0))
    out = api.query_batch("i", batch)
    assert len(out) == len(batch)
    some = False
    for pql, (res, err, bsize) in zip(batch, out):
        assert err is None, (pql, err)
        want = api.query("i", pql)
        assert len(res) == len(want) == 1
        assert _same_result(res[0], want[0]), pql
        # a Count rode a launch of its own (1); the others launch none
        assert bsize == (1 if family == "Count" else 0), pql
        some = some or (res[0] != 0 if family == "Count"
                        else len(res[0].columns()) > 0)
    assert some, "the corpus answers nothing: the comparison is empty"


def test_query_batch_compiles_no_program_query_would_not(env):
    """The same seventeen Counts through `query` and through
    `query_batch` leave the same programs in the evaluator's cache (at
    the parent the route built a vmapped program a bucket)."""
    holder, api = env
    batch = [f"Count(Union(Row(f={r % N_ROWS}), Row(g={r % 5})))"
             for r in range(17)]
    ev = api.executor._stacked
    for pql in batch:
        api.query("i", pql)
    before = set(ev._fns)
    dispatches = ev.dispatches
    out = api.query_batch("i", batch)
    assert [err for _, err, _ in out] == [None] * len(batch)
    assert set(ev._fns) == before
    assert not any(key[0] in ("countV", "planeV") for key in ev._fns)
    # one dispatch a member, as `query` makes
    assert ev.dispatches - dispatches == len(batch)


# ------------------------------------------------------------ error isolation


def test_batch_error_isolation(env):
    """One failing member (unknown field) reports its own error; every
    other member of the same batch still returns correct results."""
    holder, api = env
    queries = ["Count(Row(f=1))", "Count(Row(nosuch=1))",
               "Count(Row(f=2))", "Count(Row(f="]
    out = api.query_batch("i", queries)
    assert out[1][0] is None and out[1][1] is not None
    assert "nosuch" in str(out[1][1])
    assert out[3][0] is None and out[3][1] is not None  # parse error
    for i in (0, 2):
        res, err, _ = out[i]
        assert err is None
        assert res[0] == api.query("i", queries[i])[0]


def test_batch_fallback_keyed_not_double_translated(env):
    """Key translation mutates the call tree in place and is not
    idempotent (the second pass sees an int where it demands a string
    key): a member is translated once, whether its Count runs stacked
    or — on a single-shard index, under MIN_SHARDS — per shard, and so
    is a TopN over the same keyed field."""
    holder, api = env
    api.create_index("kd")
    api.create_field("kd", "kf", FieldOptions(keys=True))
    api.query("kd", 'Set(7, kf="abc")')
    api.query("kd", 'Set(9, kf="abc")')
    out = api.query_batch("kd", ['Count(Row(kf="abc"))', "TopN(kf)"])
    assert out[0][1] is None, out[0][1]
    assert out[1][1] is None, out[1][1]
    assert out[0][0] == api.query("kd", 'Count(Row(kf="abc"))')
    assert out[0][0] == [2]
    assert out[1][0][0][0].key == "abc"


# ------------------------------------------------------------ HTTP layer


@pytest.fixture
def srv():
    s = ServerHarness()
    yield s
    s.close()


def _seed(srv):
    srv.client.create_index("i")
    srv.client.create_field("i", "f")
    cols = [s * SHARD_WIDTH + o for s in range(N_SHARDS)
            for o in (1, 5, 9)]
    srv.client.import_bits("i", "f", [1] * len(cols), cols)
    return cols


def test_http_query_batch_route(srv):
    """POST /index/{i}/query-batch: per-slot results / errors, mixed
    families in one body."""
    _seed(srv)
    body = json.dumps({"queries": [
        "Count(Row(f=1))", "Row(f=1)", "TopN(f, n=1)",
        "Count(Row(bad=1))"]}).encode()
    out = srv.client._request("POST", "/index/i/query-batch", body)
    slots = out["results"]
    assert slots[0]["results"] == [3 * N_SHARDS]
    assert slots[1]["results"][0]["columns"] == \
        srv.client.query("i", "Row(f=1)")["results"][0]["columns"]
    assert "error" not in slots[2]
    assert "bad" in slots[3]["error"]
    # a Count carries the size of the launch it rode
    assert slots[0]["batch"] >= 1
    assert set(slots[0]) == {"results", "batch"}
    assert set(slots[3]) == {"error"}

    # a bare JSON list is the same request
    bare = srv.client._request(
        "POST", "/index/i/query-batch",
        json.dumps(["Count(Row(f=1))"]).encode())
    assert bare["results"][0]["results"] == [3 * N_SHARDS]

    with pytest.raises(Exception):
        srv.client._request("POST", "/index/i/query-batch",
                            b'{"queries": "not-a-list"}')


def test_slow_query_line_batch_attribution(srv):
    """SLOW QUERY lines carry batch= (and fused=) between fingerprint=
    and plan=; profile= stays LAST so existing parsers keep working."""
    _seed(srv)
    log = CaptureLogger()
    srv.api.long_query_time = 0.0  # everything is slow
    srv.api.logger = log
    srv.client.query("i", "Count(Row(f=1))")
    line = [ln for ln in log.lines if "SLOW QUERY" in ln][-1]
    assert " batch=" in line
    assert re.search(
        r"fingerprint=([0-9a-f]{16}) batch=\d+ fused=\d+ plan=", line)
    # plan= field parsing (pinned by test_explain) is unchanged
    assert line.split(" plan=", 1)[1].split(" profile=", 1)[0] \
        == "Count=stacked"
    json.loads(line.split("profile=", 1)[1])

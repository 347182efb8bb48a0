"""The kept row sets of a view (core/view.py `View.row_ids`,
`View.topn_candidates`): `Rows()` and the TopN candidate set answer from
one union over the view's fragments, kept until a fragment of the view
changes — and then walked again before the change's acknowledgement can
be read. Every mutator moves the view's tick after the rows and the TopN
cache it changed, so no walk is ever kept under a tick that it did not
see all of."""

import sys
import threading
import time

import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.view import (VIEW_STANDARD, fragment_topn_candidates,
                                  row_set_stats)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils import workpool
from tests.harness import ServerHarness

INDEX = "rs"
SHARDS = tuple(range(6))


def col(shard, offset):
    return shard * SHARD_WIDTH + offset


def walked(view, shards, of_fragment):
    """The union the kept set replaced: a plain loop over the fragments."""
    rows = set()
    for shard in shards:
        frag = view.fragment(shard)
        if frag is not None:
            rows.update(of_fragment(frag))
    return tuple(sorted(rows))


def assert_kept_equals_walked(view, shards):
    for use_cache in (True, False):
        got = view.topn_candidates(shards, use_cache)
        assert got == walked(
            view, shards, lambda f: fragment_topn_candidates(f, use_cache))
        assert view.topn_candidates(shards, use_cache) is got  # kept
    rows = view.row_ids(shards)
    assert rows == walked(view, shards, lambda f: f.row_ids())
    assert view.row_ids(shards) is rows
    return rows


@pytest.fixture
def h(tmp_path):
    """Field `f` (ranked cache) and mutex field `m` over shards 0..5:
    rows 1 and 2 in every shard, row 3 in shard 0 only; the kept sets
    walked once."""
    h = ServerHarness(data_dir=str(tmp_path))
    h.api.create_index(INDEX)
    h.api.create_field(INDEX, "f")
    h.api.create_field(INDEX, "m", FieldOptions.mutex_field())
    for field in ("f", "m"):
        h.api.import_bits(INDEX, field, [1] * 6 + [2] * 6 + [3],
                          [col(s, 1) for s in SHARDS]
                          + [col(s, 2) for s in SHARDS] + [col(0, 3)])
    for field in ("f", "m"):
        assert assert_kept_equals_walked(view_of(h, field), SHARDS) \
            == (1, 2, 3)
    yield h
    h.close()


def view_of(h, field):
    return h.holder.index(INDEX).field(field).view(VIEW_STANDARD)


def query(h, pql, shards=None):
    return h.api.query(INDEX, pql, shards=shards)[0]


# ---------------------------------------------------- every kind of change


def _set_bit(h):
    query(h, f"Set({col(4, 9)}, f=7)")
    return "f", (1, 2, 3, 7)


def _clear_bit(h):
    query(h, f"Clear({col(0, 3)}, f=3)")
    return "f", (1, 2)


def _mutex_move(h):
    # column 3 of shard 0 moves from row 3 to row 8: row 3 leaves
    query(h, f"Set({col(0, 3)}, m=8)")
    return "m", (1, 2, 8)


def _import_bits_set(h):
    h.api.import_bits(INDEX, "f", [5, 6], [col(2, 40), col(5, 41)])
    return "f", (1, 2, 3, 5, 6)


def _import_bits_clear(h):
    h.api.import_bits(INDEX, "f", [2] * 6, [col(s, 2) for s in SHARDS],
                      clear=True)
    return "f", (1, 3)


def _bulk_import_mutex(h):
    frag = view_of(h, "m").fragment(1)
    frag.bulk_import([9, 9], [col(1, 1), col(1, 2)])
    return "m", (1, 2, 3, 9)


def _create_fragment(h):
    # a shard no fragment of either view held: the index's list grows
    h.api.import_bits(INDEX, "f", [11], [col(9, 4)])
    return "f", (1, 2, 3, 11)


def _remove_fragment(h):
    view_of(h, "f").remove_fragment(0)  # the only holder of row 3
    return "f", (1, 2)


def _recalculate_cache(h):
    """A cache that drifted from its rows (a phantom entry), kept by the
    view, then rebuilt from storage: the candidates lose the phantom."""
    view = view_of(h, "f")
    view.fragment(3).cache.add(99, 5)
    query(h, f"Set({col(3, 60)}, f=1)")  # moves the tick: walk the drift
    assert 99 in view.topn_candidates(SHARDS)
    view.fragment(3).recalculate_cache()
    assert 99 not in view.topn_candidates(SHARDS)
    return "f", (1, 2, 3)


CHANGES = [_set_bit, _clear_bit, _mutex_move, _import_bits_set,
           _import_bits_clear, _bulk_import_mutex, _create_fragment,
           _remove_fragment, _recalculate_cache]


@pytest.mark.parametrize("change", CHANGES,
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kept_sets_equal_a_walk_after(h, change):
    walks = row_set_stats["row_set_walks"]
    field, expected = change(h)
    idx = h.holder.index(INDEX)
    shards = idx.available_shards()
    assert assert_kept_equals_walked(view_of(h, field), shards) == expected
    assert row_set_stats["row_set_walks"] > walks
    # and the executor answers from them
    assert query(h, f"Rows({field})").rows == list(expected)
    assert query(h, f"Rows({field})", shards=list(shards)).rows \
        == list(expected)


def test_a_shard_subset_is_a_walk_of_its_own(h):
    view = view_of(h, "f")
    full = view.topn_candidates(SHARDS)
    for subset in ((1, 3), (0,), (0, 5)):
        assert assert_kept_equals_walked(view, subset) \
            == ((1, 2, 3) if 0 in subset else (1, 2))
        assert query(h, "Rows(f)", shards=list(subset)).rows \
            == list(view.row_ids(subset))
    assert view.topn_candidates(SHARDS) == full


def test_unchanged_view_answers_without_a_walk(h):
    view = view_of(h, "f")
    shards = h.holder.index(INDEX).available_shards()
    rows = view.row_ids(shards)
    candidates = view.topn_candidates(shards)
    before = dict(row_set_stats)
    for i in range(3):
        assert query(h, "Rows(f)").rows == [1, 2, 3]
        assert [p.id for p in query(h, "TopN(f)")] == [1, 2, 3]
        query(h, "GroupBy(Rows(f), Rows(m))")
        # a write to another field leaves this view's sets standing
        h.api.import_bits(INDEX, "m", [4], [col(2, 70 + i)])
    assert view.row_ids(shards) is rows
    assert view.topn_candidates(shards) is candidates
    assert row_set_stats["row_set_hits"] - before["row_set_hits"] >= 9
    # `m` is written between the calls: its Rows() walks after each write
    assert row_set_stats["row_set_walks"] - before["row_set_walks"] == 2


def test_a_new_row_in_one_shard_is_seen_by_the_next_call(h):
    for _ in range(2):  # kept, and asked again
        assert query(h, "Rows(f)").rows == [1, 2, 3]
        assert 12 not in [p.id for p in query(h, "TopN(f)")]
    query(h, f"Set({col(5, 77)}, f=12)")
    assert query(h, "Rows(f)").rows == [1, 2, 3, 12]
    assert [(p.id, p.count) for p in query(h, "TopN(f)")] \
        == [(1, 6), (2, 6), (3, 1), (12, 1)]
    assert [(p.id, p.count) for p in query(h, "TopN(f, n=2)")] \
        == [(1, 6), (2, 6)]


def test_paging_and_named_ids_through_the_kept_sets(h):
    h.api.import_bits(INDEX, "f", [20, 21, 21, 30],
                      [col(1, 5), col(2, 5), col(3, 5), col(4, 5)])
    want = [1, 2, 3, 20, 21, 30]
    for _ in range(2):  # the walk, then the kept set
        assert query(h, "Rows(f)").rows == want
        assert query(h, "Rows(f, previous=2)").rows == [3, 20, 21, 30]
        assert query(h, "Rows(f, limit=2)").rows == [1, 2]
        assert query(h, "Rows(f, previous=3, limit=2)").rows == [20, 21]
        assert query(h, "Rows(f, previous=30)").rows == []
        assert query(h, f"Rows(f, column={col(2, 5)})").rows == [21]
        assert [(p.id, p.count) for p in query(
            h, "TopN(f, ids=[30, 21, 3, 404])")] == [(21, 2), (3, 1), (30, 1)]
        assert [(p.id, p.count) for p in query(
            h, "TopN(f, Row(f=21), ids=[1, 21])")] == [(21, 2)]


# -------------------------------------------- acknowledged => a candidate


def test_the_tick_moves_after_the_cache(h):
    """At the moment a fragment tells its view that it changed, its TopN
    cache already holds the change: a candidate walk that runs between
    the two is kept under the old tick, never the new one."""
    frag = view_of(h, "f").fragment(2)
    seen = []
    bump = frag.on_mutate

    def on_mutate(rows):
        seen.append(set(frag.cache.ids()))
        bump(rows)

    frag.on_mutate = on_mutate
    query(h, f"Set({col(2, 50)}, f=40)")
    assert 40 in seen[-1]
    query(h, f"Clear({col(2, 50)}, f=40)")
    assert 40 not in seen[-1]
    h.api.import_bits(INDEX, "f", [41], [col(2, 51)])
    assert 41 in seen[-1]
    h.api.import_bits(INDEX, "f", [41], [col(2, 51)], clear=True)
    assert 41 not in seen[-1]
    frag.set_row_plane(42, frag.row_plane(1))
    assert 42 in seen[-1]
    assert len(seen) == 5


def test_acknowledged_rows_are_candidates_under_concurrency(h):
    """Writers add new rows to existing fragments while readers ask for
    the TopN candidates and Rows(): a reader that starts after a write
    was acknowledged sees its row. Each write also runs a candidate walk
    in the window between its fragment's change and the view's tick
    (the on_mutate hook), where a tick that moved before the cache would
    keep the old set under the new tick."""
    view = view_of(h, "f")
    idx = h.holder.index(INDEX)
    field = idx.field("f")
    shards = idx.available_shards()
    ex = h.api.executor
    pool = workpool.get_pool()
    for shard in SHARDS:
        frag = view.fragment(shard)
        bump = frag.on_mutate

        def walk_in_the_window(rows, bump=bump):
            bump(rows)
            # inline: the writer holds its fragment's lock, which a
            # reader's Rows() task on the pool may be waiting for
            was = getattr(pool._in_worker, "active", False)
            pool._in_worker.active = True
            try:
                view.topn_candidates(shards)
            finally:
                pool._in_worker.active = was

        frag.on_mutate = walk_in_the_window

    acked = []  # rows whose write returned, in the order they did
    lock = threading.Lock()
    stop = threading.Event()
    deadline = time.monotonic() + 90
    errors = []

    def writer(k):
        try:
            for i in range(12):
                row = 100 + 20 * k + i
                query(h, f"Set({col((k + i) % len(SHARDS), 200 + i)}, f={row})")
                with lock:
                    acked.append(row)
                assert row in ex._candidate_rows(
                    field, shards, None, True, VIEW_STANDARD), row
        except Exception as e:  # noqa: BLE001 — handed to the main thread
            errors.append(e)

    def reader():
        try:
            while not stop.is_set() and time.monotonic() < deadline:
                with lock:
                    known = list(acked)
                candidates = set(ex._candidate_rows(
                    field, shards, None, True, VIEW_STANDARD))
                rows = set(query(h, "Rows(f)").rows)
                missing = [r for r in known
                           if r not in candidates or r not in rows]
                assert not missing, missing
        except Exception as e:  # noqa: BLE001 — handed to the main thread
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader, daemon=True)
                   for _ in range(4)]
        writers = [threading.Thread(target=writer, args=(k,), daemon=True)
                   for k in range(3)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=120)
        stop.set()
        for t in readers:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    assert not any(t.is_alive() for t in readers + writers)
    assert len(acked) == 36
    assert set(acked) <= set(view.topn_candidates(shards))
    assert_kept_equals_walked(view, shards)

"""The kept shard list (core/view.py ShardList): `available_shards()` of
an index, a field and a view answers from a kept tuple and walks the
fragment dictionaries again only after the set of fragments, views or
fields changed — and then before the change's acknowledgement can be
read (acknowledged => readable across a structure change).
"""

import datetime
import threading
import time

import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.index import shard_list_stats
from pilosa_tpu.roaring import Bitmap, serialize
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.harness import ServerHarness

INDEX = "sl"


def fresh_walk(level):
    """The shards of an index, field or view by the walk the kept list
    replaced: sorted over every fragment dictionary below `level`."""
    if hasattr(level, "fragments"):
        return tuple(sorted(level.fragments))
    below = getattr(level, "fields", None) or getattr(level, "views", {})
    return tuple(sorted({s for child in below.values()
                         for s in fresh_walk(child)}))


def assert_levels_match_walk(idx):
    levels = [idx]
    for field in idx.fields.values():
        levels.append(field)
        levels.extend(field.views.values())
    for level in levels:
        got = level.available_shards()
        assert isinstance(got, tuple)
        assert got == fresh_walk(level), level
    return idx.available_shards()


def count(h, pql="Count(Row(f=1))"):
    return h.api.query(INDEX, pql)[0]


@pytest.fixture
def h(tmp_path):
    """Fields f and g with row 1 set in one column of shards 0..3, the
    existence field beside them, and the lists asked for once."""
    h = ServerHarness(data_dir=str(tmp_path))
    h.api.create_index(INDEX)
    for field in ("f", "g"):
        h.api.create_field(INDEX, field)
        h.api.import_bits(INDEX, field, [1] * 4,
                          [s * SHARD_WIDTH + 3 for s in range(4)])
    assert h.holder.index(INDEX).available_shards() == (0, 1, 2, 3)
    yield h
    h.close()


def _roaring_blob(column):
    bitmap = Bitmap()
    bitmap.add(1 * SHARD_WIDTH + column % SHARD_WIDTH)
    return serialize(bitmap)


def _set_bit(h):
    h.api.query(INDEX, f"Set({6 * SHARD_WIDTH + 1}, f=1)")
    return (0, 1, 2, 3, 6)


def _import_bits(h):
    h.api.import_bits(INDEX, "f", [1, 1],
                      [5 * SHARD_WIDTH, 9 * SHARD_WIDTH + 7])
    return (0, 1, 2, 3, 5, 9)


def _import_roaring(h):
    h.api.import_roaring(INDEX, "g", 7, _roaring_blob(11))
    return (0, 1, 2, 3, 7)


def _import_values(h):
    h.api.create_field(INDEX, "n", FieldOptions.int_field(0, 1000))
    h.api.import_values(INDEX, "n", [4 * SHARD_WIDTH + 2], [17])
    return (0, 1, 2, 3, 4)


def _remove_fragment(h):
    idx = h.holder.index(INDEX)
    for field in idx.fields.values():
        for view in field.views.values():
            view.remove_fragment(3)
    return (0, 1, 2)


def _time_quantum_view(h):
    h.api.create_field(INDEX, "t", FieldOptions.time_field("YMD"))
    idx = h.holder.index(INDEX)
    assert idx.available_shards() == (0, 1, 2, 3)
    idx.field("t").set_bit(2, 8 * SHARD_WIDTH + 5,
                           timestamp=datetime.datetime(2019, 3, 4))
    assert set(idx.field("t").views) == {
        "standard", "standard_2019", "standard_201903", "standard_20190304"}
    return (0, 1, 2, 3, 8)


def _create_field_then_write(h):
    h.api.create_field(INDEX, "fresh")
    assert h.holder.index(INDEX).available_shards() == (0, 1, 2, 3)
    h.api.import_bits(INDEX, "fresh", [0], [12 * SHARD_WIDTH])
    return (0, 1, 2, 3, 12)


def _delete_only_holder_of_a_shard(h):
    h.api.create_field(INDEX, "lone")
    h.api.import_bits(INDEX, "lone", [0], [20 * SHARD_WIDTH])
    # existence tracking put shard 20 into _exists too: take it out, so
    # that `lone` is the only field that holds the shard
    h.holder.index(INDEX).existence_field().view().remove_fragment(20)
    assert h.holder.index(INDEX).available_shards() == (0, 1, 2, 3, 20)
    h.api.delete_field(INDEX, "lone")
    return (0, 1, 2, 3)


def _holder_reopen(h):
    old = h.holder.index(INDEX)
    h.api.import_bits(INDEX, "f", [1], [10 * SHARD_WIDTH])
    h.reopen()
    assert h.holder.index(INDEX) is not old
    assert old.available_shards() == ()  # closed: it holds nothing now
    return (0, 1, 2, 3, 10)


STRUCTURE_CHANGES = [
    _set_bit, _import_bits, _import_roaring, _import_values,
    _remove_fragment, _time_quantum_view, _create_field_then_write,
    _delete_only_holder_of_a_shard, _holder_reopen]


@pytest.mark.parametrize(
    "change", STRUCTURE_CHANGES, ids=lambda f: f.__name__.lstrip("_"))
def test_structure_change_is_seen_at_every_level(h, change):
    before = assert_levels_match_walk(h.holder.index(INDEX))
    assert before == (0, 1, 2, 3)
    rebuilds = shard_list_stats["shard_list_rebuilds"]
    expected = change(h)
    idx = h.holder.index(INDEX)
    assert assert_levels_match_walk(idx) == expected
    assert shard_list_stats["shard_list_rebuilds"] > rebuilds
    # and the executor plans over the new list
    assert h.api.executor._call_shards(idx, None) is idx.available_shards()


def test_unchanged_structure_answers_with_the_same_object(h):
    idx = h.holder.index(INDEX)
    levels = [idx, idx.field("f"), idx.field("f").view()]
    first = [level.available_shards() for level in levels]
    stats = dict(shard_list_stats)
    for _ in range(3):
        count(h)
        for level, kept in zip(levels, first):
            assert level.available_shards() is kept
    assert shard_list_stats == stats


def test_write_into_existing_fragments_rebuilds_nothing(h):
    idx = h.holder.index(INDEX)
    kept = idx.available_shards()
    stats = dict(shard_list_stats)
    h.api.import_bits(INDEX, "f", [1, 2, 900],
                      [17, SHARD_WIDTH + 17, 3 * SHARD_WIDTH + 17])
    h.api.import_roaring(INDEX, "g", 1, _roaring_blob(99))
    h.api.query(INDEX, f"Set({2 * SHARD_WIDTH + 40}, g=5)")
    h.api.query(INDEX, f"Clear({2 * SHARD_WIDTH + 40}, g=5)")
    assert count(h) == 5
    assert idx.available_shards() is kept
    assert shard_list_stats == stats


def test_executor_hands_on_the_kept_tuple_and_copies_named_shards(h):
    idx = h.holder.index(INDEX)
    ex = h.api.executor
    assert ex._call_shards(idx, None) is idx.available_shards()
    named = [2, 0]
    out = ex._call_shards(idx, named)
    assert out == [2, 0] and out is not named


def test_walk_that_raced_a_writer_is_thrown_away(h):
    """A fragment that arrives during a walk, after the walk read its
    dictionary: the stale result is kept under the tick read before the
    walk, which the writer moved, so the next call walks again."""
    idx = h.holder.index(INDEX)
    view = idx.field("f").view()
    walk = type(view)._walk_shards

    def racing_walk():
        shards = walk(view)
        del view._walk_shards
        view.create_fragment_if_not_exists(30)
        return shards

    view.remove_fragment(0)  # the view has to walk at all
    view._walk_shards = racing_walk
    assert 30 not in idx.available_shards()
    assert idx.available_shards() == (0, 1, 2, 3, 30)
    assert view.available_shards() == (1, 2, 3, 30)


def test_debug_vars_holder_carries_the_counters(h):
    def read():
        return h.client._request("GET", "/debug/vars")["holder"]

    first = read()
    assert first["shard_list_rebuilds"] >= 1
    assert first["shard_list_seconds"] > 0
    count(h)
    assert read() == first
    h.api.import_bits(INDEX, "f", [1], [15 * SHARD_WIDTH])
    assert count(h) == 5
    second = read()
    assert second["shard_list_rebuilds"] == first["shard_list_rebuilds"] + 1
    assert second["shard_list_seconds"] > first["shard_list_seconds"]


def test_acknowledged_is_readable_across_structure_changes(h):
    """Four readers Count without naming shards while a writer imports
    one bit into shards 8..40 in turn: after each acknowledgement the
    writer's own Count has the bit, and no reader ever sees the count
    fall."""
    deadline = time.monotonic() + 120
    stop = threading.Event()
    errors = []

    def reader():
        last = 0
        try:
            while not stop.is_set() and time.monotonic() < deadline:
                n = count(h)
                assert n >= last, (n, last)
                last = n
        except Exception as e:  # noqa: BLE001 — handed to the main thread
            errors.append(e)

    readers = [threading.Thread(target=reader, daemon=True)
               for _ in range(4)]
    for t in readers:
        t.start()
    try:
        expected = count(h)
        for shard in range(8, 41):
            assert time.monotonic() < deadline, "time limit"
            h.api.import_bits(INDEX, "f", [1], [shard * SHARD_WIDTH + 9])
            expected += 1
            assert count(h) == expected, shard
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=30)
    assert not errors, errors
    assert not any(t.is_alive() for t in readers)
    idx = h.holder.index(INDEX)
    assert idx.available_shards() == (0, 1, 2, 3, *range(8, 41))
    assert assert_levels_match_walk(idx) == idx.available_shards()

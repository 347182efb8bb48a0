"""The row-granular fingerprint the stack cache rests on (ISSUE 29).

Fragment: if any bit of row r changed, (uid, row_generation(r)) changed —
held over every mutator; the mutators that know their rows leave the
others' generations alone; a write that changes nothing moves nothing.
View: stamp(row) moves with that row and with the set of fragments,
stamp() with anything."""

import numpy as np
import pytest

from pilosa_tpu.core import fragment as fragment_mod
from pilosa_tpu.core import view as view_mod
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.view import View
from pilosa_tpu.roaring import Bitmap, serialize
from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

ROWS = range(12)     # watched; BSI rows 0..9 at depth 8 are among them
DEPTH = 8


def _blob(positions):
    bitmap = Bitmap()
    bitmap.add_many(np.asarray(positions, dtype=np.uint64))
    return serialize(bitmap, optimize=False)


def _pos(row, col):
    return row * SHARD_WIDTH + col


def _plane(cols):
    words = np.zeros(WORDS_PER_ROW, dtype=np.uint32)
    for c in cols:
        words[c // 32] |= np.uint32(1 << (c % 32))
    return words


# name -> (mutexed, seed(f), mutate(f), the rows the write names).
# `seed` puts bits in place first; `mutate` is the write under test, and
# run a second time it has to be a no-op. A batch may name a row in which
# it changes nothing (a bit already clear): that row may go stale with
# the others, coarser and safe. Rows the write does not name never do.
BSI_ROWS = set(range(DEPTH + 2))
MUTATORS = {
    "set_bit": (
        False, lambda f: f.set_bit(2, 7),
        lambda f: f.set_bit(1, 5),
        {1}),
    "clear_bit": (
        False, lambda f: [f.set_bit(1, 5), f.set_bit(2, 5)],
        lambda f: f.clear_bit(1, 5),
        {1}),
    "set_bit.mutex_moves_the_column": (
        True, lambda f: [f.set_bit(3, 50), f.set_bit(4, 9)],
        lambda f: f.set_bit(7, 50),
        {3, 7}),
    "import_positions.set": (
        False, lambda f: f.set_bit(2, 7),
        lambda f: f.import_positions(
            [_pos(1, 3), _pos(1, 70000), _pos(5, 3)], []),
        {1, 5}),
    "import_positions.clear": (
        False, lambda f: f.import_positions(
            [_pos(r, c) for r in (1, 2, 5) for c in (3, 9)], []),
        lambda f: f.import_positions([], [_pos(1, 3), _pos(5, 9)]),
        {1, 5}),
    "import_positions.set_and_clear": (
        False, lambda f: f.import_positions(
            [_pos(r, c) for r in (1, 2, 5) for c in (3, 9)], []),
        lambda f: f.import_positions([_pos(6, 1)], [_pos(2, 3)]),
        {2, 6}),
    "bulk_import.set": (
        False, lambda f: f.set_bit(2, 7),
        lambda f: f.bulk_import([1, 1, 4], [10, 11, 12]),
        {1, 4}),
    "bulk_import.clear": (
        False, lambda f: f.bulk_import([1, 1, 4, 2], [10, 11, 12, 13]),
        lambda f: f.bulk_import([1, 4], [10, 12], clear=True),
        {1, 4}),
    "bulk_import.mutex": (
        True, lambda f: f.bulk_import([1, 2, 3], [60, 61, 62]),
        # column 60 moves 1 -> 5, 61 stays in 2, 63 is new in 6
        lambda f: f.bulk_import([5, 2, 6], [60, 61, 63]),
        {1, 5, 6}),
    "import_roaring.set": (
        False, lambda f: f.set_bit(2, 7),
        lambda f: f.import_roaring(
            _blob([_pos(1, 3), _pos(1, 200000), _pos(8, 4)])),
        {1, 8}),
    "import_roaring.clear": (
        False, lambda f: f.import_roaring(
            _blob([_pos(r, c) for r in (1, 2, 8) for c in (3, 4)])),
        # row 9 holds nothing: named all the same
        lambda f: f.import_roaring(
            _blob([_pos(1, 3), _pos(8, 4), _pos(9, 4)]), clear=True),
        {1, 8, 9}),
    "set_row_plane": (
        False, lambda f: [f.set_bit(4, 1), f.set_bit(4, 2), f.set_bit(2, 1)],
        lambda f: f.set_row_plane(4, _plane([3])),
        {4}),
    "set_value": (
        False, lambda f: f.set_value(9, DEPTH, 0b1010),
        lambda f: f.set_value(9, DEPTH, -0b0110),
        BSI_ROWS),
    "set_value.new_column": (
        False, lambda f: f.set_value(9, DEPTH, 3),
        lambda f: f.set_value(11, DEPTH, 0b100),
        BSI_ROWS),
    "clear_value": (
        False, lambda f: [f.set_value(9, DEPTH, 0b1010),
                          f.set_value(10, DEPTH, 0b0101)],
        lambda f: f.clear_value(9, DEPTH),
        BSI_ROWS),
}


def _snapshot(f):
    return ({r: np.array(f.row_plane(r), copy=True) for r in ROWS},
            {r: f.row_generation(r) for r in ROWS}, f.generation)


def _moved(before, after):
    return {r for r in ROWS if not np.array_equal(before[r], after[r])}


@pytest.fixture
def fragment(tmp_path, request):
    mutexed = MUTATORS[request.param][0]
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0,
                 mutexed=mutexed).open()
    told = []
    f.on_mutate = told.append
    yield f, told, request.param
    f.close()


@pytest.mark.parametrize("fragment", sorted(MUTATORS), indirect=True)
def test_a_changed_plane_has_a_new_row_generation(fragment):
    f, told, name = fragment
    _, seed, mutate, named = MUTATORS[name]
    seed(f)
    planes0, gens0, gen0 = _snapshot(f)
    del told[:]
    mutate(f)
    planes1, gens1, gen1 = _snapshot(f)
    moved = _moved(planes0, planes1)
    assert moved, "the case has to change something"
    assert moved <= named < set(ROWS)
    assert gen1 > gen0
    for r in ROWS:
        if r in moved:
            assert gens1[r] > gens0[r], f"row {r} changed unseen"
        elif r not in named:
            assert gens1[r] == gens0[r], f"row {r} staled for nothing"
    # the owner is told of the rows that moved and of none the write
    # does not name (a set and a clear batch tell of theirs only where
    # the batch changed a bit)
    assert moved <= set().union(*told) <= named
    # the same write again changes no bit and moves nothing
    del told[:]
    mutate(f)
    planes2, gens2, gen2 = _snapshot(f)
    assert not _moved(planes1, planes2)
    assert (gens2, gen2) == (gens1, gen1)
    assert told == []


def test_a_mutation_of_unknown_extent_raises_the_floor(tmp_path):
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    told = []
    f.on_mutate = told.append
    f.set_bit(1, 5)
    f.set_bit(2, 5)
    _, gens0, _ = _snapshot(f)
    assert gens0[1] < gens0[2] and gens0[3] == 0
    f._invalidate_all_rows()
    _, gens1, gen1 = _snapshot(f)
    assert all(gens1[r] == gen1 > gens0[r] for r in ROWS)
    assert told[-1] is None
    # a row written afterwards moves alone again
    f.set_bit(1, 6)
    assert f.row_generation(1) == f.generation > gen1
    assert f.row_generation(2) == gen1
    f.close()


def test_rows_remembered_are_bounded_and_coarser_is_all_it_costs(
        tmp_path, monkeypatch):
    monkeypatch.setattr(fragment_mod, "ROW_GENERATIONS_MAX", 4)
    f = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0).open()
    for row in range(40):
        before = {r: f.row_generation(r) for r in range(40)}
        f.set_bit(row, row)
        assert f.row_generation(row) > before[row]
        assert len(f._row_gens[1]) <= 4
        for r in range(40):     # never backwards, whatever was forgotten
            assert f.row_generation(r) >= before[r]
    # a batch wider than the bound goes straight to the floor
    f.import_positions([_pos(r, 99) for r in range(10)], [])
    assert f._row_gens == (f.generation, {})
    f.close()


# ------------------------------------------------------------------- view


@pytest.fixture
def view(tmp_path):
    v = View(str(tmp_path / "views" / "standard"), "i", "f", "standard")
    v.open()
    v.set_bit(1, 3)
    v.set_bit(2, SHARD_WIDTH + 3)
    yield v
    v.close()


def test_a_leaf_stamp_moves_with_its_row_alone(view):
    one, two, any_ = view.stamp(1), view.stamp(2), view.stamp()
    view.set_bit(100, 4)                      # another row, same fragment
    assert view.stamp(1) == one and view.stamp(2) == two
    assert view.stamp() != any_
    assert view.stamp(100) != (view.uid, one[1], 0)
    view.set_bit(1, 5)
    assert view.stamp(1) != one and view.stamp(2) == two
    one = view.stamp(1)
    view.set_bit(1, 5)                        # no bit changes
    assert view.stamp(1) == one
    view.fragment(0).import_positions([_pos(2, 9), _pos(7, 9)], [])
    assert view.stamp(1) == one and view.stamp(2) != two


def test_the_set_of_fragments_moves_every_stamp(view):
    for change in (lambda: view.create_fragment_if_not_exists(5),
                   lambda: view.remove_fragment(5),
                   lambda: view.fragment(0)._invalidate_all_rows()):
        one, any_ = view.stamp(1), view.stamp()
        change()
        assert view.stamp(1) != one
        assert view.stamp() != any_
    assert view.remove_fragment(77) is None   # nothing there: nothing moves
    one = view.stamp(1)
    view.remove_fragment(77)
    assert view.stamp(1) == one


def test_a_view_made_again_never_repeats_a_stamp(tmp_path):
    path = str(tmp_path / "views" / "standard")
    seen = set()
    for _ in range(3):
        v = View(path, "i", "f", "standard")
        v.open()
        v.set_bit(1, 3)
        for stamp in (v.stamp(1), v.stamp()):
            assert stamp not in seen
            seen.add(stamp)
        v.close()


def test_row_stamps_are_bounded(view, monkeypatch):
    monkeypatch.setattr(view_mod, "ROW_STAMPS_MAX", 4)
    seen = {view.stamp(1)}
    for row in range(3, 40):
        view.set_bit(row, row)
        assert len(view._row_stamps[1]) <= 4
    view.set_bit(1, 77)
    assert view.stamp(1) not in seen


def test_writers_racing_in_one_view_never_leave_a_stamp_where_it_was(view):
    """16 threads, each writing row 1 in a fragment of its own (so only
    the view's fingerprint is shared): after a write returns, stamp(1)
    and stamp() are none of the values the writer saw before it began —
    what a lost update to a shared counter would break."""
    import sys
    import threading

    def writer(shard):
        try:
            seen_row, seen_any = set(), set()
            for k in range(150):
                seen_row.add(view.stamp(1))
                seen_any.add(view.stamp())
                assert view.set_bit(1, shard * SHARD_WIDTH + 100 + k)
                if view.stamp(1) in seen_row or view.stamp() in seen_any:
                    repeats.append((shard, k))
        except Exception as exc:  # noqa: BLE001 — the assert below says it
            errors.append(repr(exc))

    repeats, errors = [], []
    for shard in range(16):
        view.create_fragment_if_not_exists(shard)
    two = view.stamp(2)
    threads = [threading.Thread(target=writer, args=(s,), daemon=True)
               for s in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not repeats, (errors, repeats[:5])
    assert view.stamp(2) == two       # nobody wrote row 2

"""View: a layout variant of a field, grouping per-shard fragments.

Reference: view.go:44. Names: "standard", time-quantum views
("standard_2019", ...), and "bsig_<field>" for BSI integer storage
(view.go:27-41).
"""

import itertools
import os
import threading
import time

from ..utils.workpool import shard_map_reduce
from .fragment import Fragment

_view_uids = itertools.count(1)
_structure_ticks = itertools.count(1)  # ShardList's, one for every level

# Rows a view keeps a change tick of its own for (see View.stamp); past
# it the structure tick moves instead, which every leaf stack of the view
# answers with one generation walk.
ROW_STAMPS_MAX = 4096

VIEW_STANDARD = "standard"
VIEW_BSI_GROUP_PREFIX = "bsig_"

# Walks of the views' kept row sets (View.row_ids / topn_candidates), the
# walks' own seconds, and the calls a kept set answered without a walk,
# over every view of the process (/debug/vars holder).
row_set_stats = {"row_set_walks": 0, "row_set_hits": 0,
                 "row_set_seconds": 0.0}


def fragment_topn_candidates(frag, use_cache=True):
    """THE per-fragment TopN candidate policy: cache ids when a cache is
    populated (the reference's approximation), else every present row.
    Shared by View.topn_candidates and the SPMD data plane."""
    if use_cache and frag.cache is not None and len(frag.cache):
        return frag.cache.ids()
    return frag.row_ids()


class ShardList:
    """The sorted shards of one level (view, field, index), kept until
    the level's structure changes: a query that names no shards asks
    the index for this once per call, and a walk of every fragment
    dictionary costs more than the rest of its plan at 954 shards.

    `_kept` = (structure tick the walk started under, its result) is
    ONE tuple; `structure_changed` is called AFTER the dictionary
    changed, by the thread that changed it, and moves the level's tick
    before its parent's. So a reader takes no lock: a walk that raced a
    writer is stored under the tick it read first, which the writer has
    moved since, and the next call walks again; a reader that comes
    after the parent's tick moved finds every level below moved too.
    Ticks come from one itertools.count (atomic next()), so a slot never
    holds one value twice, whatever order racing writers store in. The
    result is one immutable tuple, the same object until the structure
    changes: callers share it and must not expect a copy.
    """

    on_structure = None  # the parent level's structure_changed
    _structure = 0
    _kept = (None, ())

    def structure_changed(self):
        self._structure = next(_structure_ticks)
        if self.on_structure is not None:
            self.on_structure()

    def available_shards(self):
        tick, shards = self._kept
        if tick == self._structure:
            return shards
        tick = self._structure
        shards = self._walk_shards()
        self._kept = (tick, shards)
        return shards

    def _walk_shards(self):
        """Union of the levels below (field, index)."""
        shards = set()
        for child in list(self._shard_children().values()):
            shards.update(child.available_shards())
        return tuple(sorted(shards))


class View(ShardList):
    def __init__(self, path, index, field, name, max_op_n=None,
                 snapshot_queue=None, mutexed=False, cache_type="none",
                 cache_size=0):
        self.path = path  # .../<field>/views/<name>
        self.index = index
        self.field = field
        self.name = name
        self.mutexed = mutexed
        self.max_op_n = max_op_n
        self.snapshot_queue = snapshot_queue
        # BSI views never cache (only row-oriented views serve TopN)
        self.cache_type = ("none" if name.startswith(VIEW_BSI_GROUP_PREFIX)
                           else cache_type)
        self.cache_size = cache_size
        self.fragments = {}  # shard -> Fragment
        self._lock = threading.RLock()
        # O(1) change fingerprints for the stacked serving caches, so a
        # cache hit costs one compare instead of a per-shard generation
        # walk (exec/stacked.py two-level fingerprint). `mutations` moves
        # on ANY fragment mutation, creation or removal in this view;
        # `_row_stamps` = (structure tick, {row: tick of its last change})
        # moves the structure tick where the extent is unknown (a fragment
        # created, removed or closed; a mutator that names no rows) and
        # one row's tick where the mutator named the row. The pair is ONE
        # tuple, replaced whole with the structure tick, so a lock-free
        # reader never pairs a new structure tick with the old rows. Every
        # value comes from one itertools.count (atomic next()): a slot
        # never holds the same value twice, whatever order racing writers
        # store in. uid distinguishes a recreated view (drop + re-create)
        # whose counters restart.
        self.uid = next(_view_uids)
        self._ticks = itertools.count(1)
        self.mutations = 0
        self._row_stamps = (0, {})
        # The kept unions over the fragments (row_ids, topn_candidates):
        # ONE tuple each, (mutation tick read before the walk, shards,
        # sorted row ids). Every mutator moves `mutations` after the rows
        # and the TopN cache it changed, so a walk that raced a writer is
        # kept under a tick the writer has moved since, and the next call
        # walks again; a reader takes no lock.
        self._kept_rows = (None, None, ())
        self._kept_candidates = (None, None, ())

    def open(self):
        frag_dir = os.path.join(self.path, "fragments")
        os.makedirs(frag_dir, exist_ok=True)
        for name in sorted(os.listdir(frag_dir)):
            if name.endswith(".snapshotting") or name.endswith(".cache"):
                continue
            try:
                shard = int(name)
            except ValueError:
                continue
            self._new_fragment(shard)
        return self

    def close(self):
        with self._lock:
            for f in self.fragments.values():
                f.close()
            self.fragments.clear()
            self._bump_mutations()
            self.structure_changed()

    def remove_fragment(self, shard):
        """Detach and return one fragment (resize cleanup). Bumps the
        mutation counter — removal changes what cached serving stacks
        must contain, exactly like a write (exec/stacked.py stamp)."""
        with self._lock:
            frag = self.fragments.pop(shard, None)
            if frag is not None:
                self._bump_mutations()
                self.structure_changed()
            return frag

    def fragment_path(self, shard):
        return os.path.join(self.path, "fragments", str(shard))

    def _new_fragment(self, shard):
        """Open a fragment, then publish it: a reader never finds one
        whose rows and cache have not been read from disk yet."""
        kwargs = {}
        if self.max_op_n is not None:
            kwargs["max_op_n"] = self.max_op_n
        frag = Fragment(
            self.fragment_path(shard), self.index, self.field, self.name,
            shard, snapshot_queue=self.snapshot_queue, mutexed=self.mutexed,
            cache_type=self.cache_type, cache_size=self.cache_size,
            **kwargs)
        frag.on_mutate = self._bump_mutations
        frag.open()
        self.fragments[shard] = frag
        self._bump_mutations()
        self.structure_changed()
        return frag

    def _bump_mutations(self, rows=None):
        """A fragment of this view changed (its own generations moved
        first): `rows` are the rows it touched, None = extent unknown or
        the set of fragments itself changed. Lock-free against readers
        and other writers — a stamp read early means one extra generation
        walk in the serving cache, never a stale result (the per-shard
        gens remain the ground truth)."""
        self.mutations = next(self._ticks)
        own = self._row_stamps[1]
        if rows is None or len(own) + len(rows) > ROW_STAMPS_MAX:
            self._row_stamps = (next(self._ticks), {})
        else:
            for row_id in rows:
                own[row_id] = next(self._ticks)

    def stamp(self, row_id=None):
        """First-level fingerprint of a cached serving stack: equal
        stamps prove that nothing the stack holds changed in between. A
        stack of one row (a leaf) names the row and is answered from
        (uid, structure tick, the row's tick): writes to other rows of
        the view leave it alone. Stacks of many rows (row chunks, BSI
        planes) name none and get (uid, mutations)."""
        if row_id is None:
            return self.uid, self.mutations
        structure, rows = self._row_stamps
        return self.uid, structure, rows.get(row_id, 0)

    def fragment(self, shard):
        return self.fragments.get(shard)

    def create_fragment_if_not_exists(self, shard):
        """(reference: view.CreateFragmentIfNotExists view.go:263)"""
        with self._lock:
            frag = self.fragments.get(shard)
            if frag is None:
                frag = self._new_fragment(shard)
            return frag

    def _walk_shards(self):
        return tuple(sorted(self.fragments))

    # -- kept row sets --------------------------------------------------------

    def row_ids(self, shards):
        """Sorted union of the fragments' row ids over `shards` (Rows()),
        kept until a fragment of the view changes. An immutable tuple,
        the same object until then: callers must not expect a copy."""
        return self._kept_union("_kept_rows", shards, Fragment.row_ids)

    def topn_candidates(self, shards, use_cache=True):
        """Sorted union of fragment_topn_candidates over `shards` (the
        TopN candidate set), kept as row_ids is."""
        if not use_cache:  # the policy is then every present row
            return self.row_ids(shards)
        return self._kept_union("_kept_candidates", shards,
                                fragment_topn_candidates)

    def _kept_union(self, slot, shards, of_fragment):
        tick = self.mutations  # before the walk: see _kept_rows
        if not isinstance(shards, tuple):
            shards = tuple(shards)
        kept_tick, kept_shards, rows = getattr(self, slot)
        if kept_tick == tick and (kept_shards is shards
                                  or kept_shards == shards):
            row_set_stats["row_set_hits"] += 1
            return rows
        t0 = time.perf_counter()

        def shard_rows(shard):
            frag = self.fragments.get(shard)
            return None if frag is None else of_fragment(frag)

        found = set()
        for ids in shard_map_reduce(shards, shard_rows):
            if ids is not None:
                found.update(ids)
        rows = tuple(sorted(found))
        setattr(self, slot, (tick, shards, rows))
        row_set_stats["row_set_walks"] += 1
        row_set_stats["row_set_seconds"] += time.perf_counter() - t0
        return rows

    # -- routed ops ---------------------------------------------------------

    def set_bit(self, row_id, column_id):
        from ..shardwidth import SHARD_WIDTH

        shard = column_id // SHARD_WIDTH
        return self.create_fragment_if_not_exists(shard).set_bit(row_id, column_id)

    def clear_bit(self, row_id, column_id):
        from ..shardwidth import SHARD_WIDTH

        shard = column_id // SHARD_WIDTH
        frag = self.fragment(shard)
        if frag is None:
            return False
        return frag.clear_bit(row_id, column_id)

    def set_value(self, column_id, bit_depth, value):
        from ..shardwidth import SHARD_WIDTH

        shard = column_id // SHARD_WIDTH
        return self.create_fragment_if_not_exists(shard).set_value(
            column_id, bit_depth, value)

    def clear_value(self, column_id, bit_depth):
        from ..shardwidth import SHARD_WIDTH

        shard = column_id // SHARD_WIDTH
        frag = self.fragment(shard)
        if frag is None:
            return False
        return frag.clear_value(column_id, bit_depth)

    def value(self, column_id, bit_depth):
        from ..shardwidth import SHARD_WIDTH

        shard = column_id // SHARD_WIDTH
        frag = self.fragment(shard)
        if frag is None:
            return 0, False
        return frag.value(column_id, bit_depth)

"""Fragment: the (index, field, view, shard) storage unit.

Reference: fragment.go:100. There, a fragment is an mmap'd roaring file plus
an appended op log; bit position = rowID*ShardWidth + colID%ShardWidth
(fragment.go:3090). Here the same roaring file (+WAL) is the at-rest format,
while the query-time representation is dense row planes in device HBM:
`row_device(rowID)` densifies the row's containers into a [WORDS_PER_ROW]
uint32 array and caches it on device, invalidated by writes. All set algebra
on those planes happens in the executor via pilosa_tpu.ops.

Durability model (reference: fragment.go:2311-2395, roaring op log):
  file = roaring snapshot ++ op log. Every mutation appends an op record;
  when the op count exceeds max_op_n (default 10k) the fragment is
  snapshotted (file rewritten via temp+rename, op log reset).
"""

import itertools
import os
import hashlib
import threading
from collections import OrderedDict

import numpy as np

_fragment_uids = itertools.count(1)

# Cross-fragment LRU of resident mutex rows-vectors (~8 MB each; see
# Fragment._mutex_vector). 64 bounds worst-case host RAM at ~512 MB.
_MUTEX_VECTOR_CAP = 64
_MUTEX_VECTOR_LOCK = threading.Lock()
_MUTEX_VECTORS = OrderedDict()

from ..roaring import (
    Bitmap,
    OP_ADD,
    OP_ADD_BATCH,
    OP_ADD_ROARING,
    OP_REMOVE,
    OP_REMOVE_BATCH,
    OP_REMOVE_ROARING,
    deserialize,
    encode_op,
    merge_bitmaps,
    serialize,
)
from ..shardwidth import (
    CONTAINERS_PER_SHARD,
    SHARD_WIDTH,
    WORDS_PER_CONTAINER,
    WORDS_PER_ROW,
)
from ..storage import oplog as oplog_mod
from ..utils import faultpoints

# Number of rows per merkle hash block (reference: fragment.go:80).
HASH_BLOCK_SIZE = 100

# Default op threshold before snapshotting (reference: fragment.go:85).
DEFAULT_MAX_OP_N = 10_000

# Rows a fragment remembers a generation of its own for (see
# Fragment.row_generation). Past it the floor rises instead: every row of
# the fragment reads as changed once, and the memory stays bounded under
# an ingest that walks a high-cardinality field.
ROW_GENERATIONS_MAX = 1024

# BSI row layout (reference: fragment.go:91-93).
BSI_EXISTS_BIT = 0
BSI_SIGN_BIT = 1
BSI_OFFSET_BIT = 2

# Boolean field rows (reference: fragment.go:88-89).
FALSE_ROW_ID = 0
TRUE_ROW_ID = 1


def _rows_of(positions):
    """Distinct row ids of a non-empty uint64 array of storage positions.
    Imports come row by row, so runs of one row collapse first (O(n), no
    sort) and the set is built from what is left."""
    rows = positions // np.uint64(SHARD_WIDTH)
    starts = np.concatenate(([True], rows[1:] != rows[:-1]))
    return set(rows[starts].tolist())


class Fragment:
    def __init__(self, path, index, field, view, shard,
                 max_op_n=DEFAULT_MAX_OP_N, snapshot_queue=None, mutexed=False,
                 cache_type="none", cache_size=0):
        from .cache import new_cache

        self.path = path
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.max_op_n = max_op_n
        self.snapshot_queue = snapshot_queue
        self.mutexed = mutexed
        # TopN candidate cache (reference: fragment.cache fragment.go:129)
        self.cache = new_cache(cache_type, cache_size)

        self.storage = Bitmap()
        self.op_n = 0
        self.flags = 0
        self._file = None
        self._snapshot_pending = False
        self._row_ids_cache = None
        # Mutex rows-vector: column offset -> row id, built lazily and
        # maintained incrementally so single-bit mutex writes are O(1)
        # instead of probing every row (reference: rowsVector
        # fragment.go:3102). None = not built / invalidated by a bulk op.
        self._mutex_vec = None
        self._lock = threading.RLock()

        # Device plane cache: rowID -> jax array; bumped generation
        # invalidates derived stacks. uid is process-unique so caches keyed
        # by (uid, generation) can never confuse a recreated fragment
        # (same path, fresh counter) with its predecessor.
        self._row_cache = {}
        self.generation = 0
        # (floor, {row: generation at its last change}) — the row-granular
        # fingerprint behind row_generation(). ONE tuple, replaced whole
        # when the floor rises, so a lock-free reader never pairs a new
        # floor with the old rows or the reverse.
        self._row_gens = (0, {})
        self.uid = next(_fragment_uids)
        # optional owner hook (View._bump_mutations), called with the rows
        # a mutation touched (None = extent unknown): lets a container
        # keep O(1) changed-since fingerprints for serving caches
        self.on_mutate = None

        # Block checksums cache (anti-entropy; reference fragment.checksums).
        self._checksums = {}

    # -- lifecycle ----------------------------------------------------------

    def open(self):
        with self._lock:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            if os.path.exists(self.path) and os.path.getsize(self.path) > 0:
                with open(self.path, "rb") as f:
                    data = f.read()
                self.storage, self.flags, self.op_n = deserialize(data)
                if self.op_n > self.max_op_n:
                    self._snapshot_locked()
            else:
                # Fresh fragment: seed the file with an empty-bitmap snapshot
                # header so appended WAL ops always follow a valid roaring
                # section (the reference's file is likewise snapshot ++ ops).
                with open(self.path, "wb") as f:
                    f.write(serialize(self.storage, flags=self.flags))
            if self._file is None:  # _snapshot_locked may have opened it
                self._file = open(self.path, "ab")
            from .cache import load_cache

            load_cache(self.cache, self.cache_path)
            # Staleness guard: a populated fragment with an empty cache
            # (pre-cache data dir, lost .cache file) would otherwise serve
            # TopN from whatever rows get written next — rebuild instead.
            if (self.cache is not None and len(self.cache) == 0
                    and self.storage.count() > 0):
                self.recalculate_cache()
        return self

    @property
    def cache_path(self):
        return self.path + ".cache"

    def flush_cache(self):
        """(reference: fragment.FlushCache fragment.go:2397)"""
        from .cache import save_cache

        with self._lock:
            save_cache(self.cache, self.cache_path)

    def recalculate_cache(self):
        """Rebuild cached counts from storage (reference:
        fragment.RecalculateCache fragment.go:2389)."""
        if self.cache is None:
            return
        with self._lock:
            self.cache.clear()
            for row_id in self.row_ids():
                self.cache.add(row_id, self.row_count(row_id))
            # no row changed, the TopN candidates may have: the view's
            # tick moves and its row stamps stay (View._bump_mutations)
            if self.on_mutate is not None:
                self.on_mutate(())

    def close(self):
        with self._lock:
            self.flush_cache()
            if self._file:
                if oplog_mod.fsync_policy() != "never":
                    oplog_mod.fsync_file(self._file)
                self._file.close()
                self._file = None
            self._row_cache.clear()
        self._drop_mutex_vec()

    def sync(self):
        """Force the WAL tail to disk regardless of fsync policy (used by
        the oplog checkpoint: fragments must be durable before the log
        above them truncates)."""
        with self._lock:
            if self._file is not None:
                oplog_mod.fsync_file(self._file)

    @property
    def is_open(self):
        return self._file is not None

    # -- positions ----------------------------------------------------------

    def pos(self, row_id, column_id):
        """Bit position in storage (reference: fragment.pos fragment.go:3090)."""
        if column_id // SHARD_WIDTH != self.shard:
            raise ValueError(
                f"column:{column_id} out of bounds for shard {self.shard}")
        return row_id * SHARD_WIDTH + column_id % SHARD_WIDTH

    # -- single-bit mutation -------------------------------------------------

    def set_bit(self, row_id, column_id):
        with self._lock:
            if self.mutexed:
                self._handle_mutex(row_id, column_id)
            return self._set_bit_locked(row_id, column_id)

    def _set_bit_locked(self, row_id, column_id):
        pos = self.pos(row_id, column_id)
        changed = self.storage.add(pos)
        if changed:
            # local ref: a concurrent LRU eviction may null the attribute
            # mid-write; mutating the discarded array is harmless (the
            # rebuild re-reads storage)
            vec = self._mutex_vec
            if self.mutexed and vec is not None:
                vec[column_id % SHARD_WIDTH] = row_id
            self._append_op(encode_op(OP_ADD, value=pos))
            self._cache_update(row_id)  # before the view's tick moves
            self._invalidate_row(row_id)
        return changed

    def clear_bit(self, row_id, column_id):
        with self._lock:
            return self._clear_bit_locked(row_id, column_id)

    def _clear_bit_locked(self, row_id, column_id):
        pos = self.pos(row_id, column_id)
        changed = self.storage.remove(pos)
        if changed:
            vec = self._mutex_vec  # local ref: see _set_bit_locked
            if self.mutexed and vec is not None:
                off = column_id % SHARD_WIDTH
                if int(vec[off]) == row_id:
                    vec[off] = -1
            self._append_op(encode_op(OP_REMOVE, value=pos))
            self._cache_update(row_id)  # before the view's tick moves
            self._invalidate_row(row_id)
        return changed

    def _handle_mutex(self, row_id, column_id):
        """Clear this column from any other row (reference: handleMutex
        fragment.go:670 via mutexVector)."""
        existing = self.row_for_column(column_id)
        if existing is not None and existing != row_id:
            self._clear_bit_locked(existing, column_id)

    def _drop_mutex_vec(self):
        """Null the rows-vector AND release its LRU slot — a
        vector-less fragment left registered would consume cap budget and
        evict live vectors (close() and every bulk-invalidation route
        through here)."""
        self._mutex_vec = None
        with _MUTEX_VECTOR_LOCK:
            _MUTEX_VECTORS.pop(self.uid, None)

    def _mutex_vector(self):
        """The mutex rows-vector (column offset -> row id, int64 array of
        SHARD_WIDTH with -1 = unset, ~8 MB/fragment), built lazily with one
        slice_range pass per row, then maintained incrementally by
        _set_bit_locked/_clear_bit_locked (bulk ops invalidate or patch
        it). O(1) lookups replace the per-write all-rows probe (reference:
        rowsVector fragment.go:3102, boltRowsVector). Mutex fragments only
        — non-mutexed fragments have no single-row-per-column invariant
        and their writes don't maintain the vector.

        Resident vectors are LRU-bounded ACROSS fragments
        (_MUTEX_VECTOR_CAP): a node holding hundreds of mutex fragments
        that each saw one write must not pin hundreds x 8 MB of host RAM.
        Eviction is a plain cross-thread `_mutex_vec = None` — safe
        because the vector is a pure cache of storage and every user
        holds a LOCAL reference under its own fragment lock (a lost
        update to a discarded array is harmless; the rebuild re-reads
        storage)."""
        vec = self._mutex_vec
        if vec is None:
            # int64: row ids range to ~2^44 (pos() is uint64); int32 would
            # overflow at row >= 2^31
            vec = np.full(SHARD_WIDTH, -1, dtype=np.int64)
            for row_id in self.row_ids():
                base = row_id * SHARD_WIDTH
                offs = (self.storage.slice_range(
                    base, base + SHARD_WIDTH) - np.uint64(base)
                ).astype(np.int64)
                vec[offs] = row_id
            self._mutex_vec = vec
        with _MUTEX_VECTOR_LOCK:
            _MUTEX_VECTORS[self.uid] = self
            _MUTEX_VECTORS.move_to_end(self.uid)
            while len(_MUTEX_VECTORS) > _MUTEX_VECTOR_CAP:
                _, victim = _MUTEX_VECTORS.popitem(last=False)
                victim._mutex_vec = None  # rebuilt lazily on next use
        return vec

    def row_for_column(self, column_id):
        """Row containing the column, or None — O(1) mutex rows-vector
        lookup (reference: rowsVector fragment.go:3102); falls back to a
        storage scan on non-mutexed fragments (no maintained vector)."""
        with self._lock:
            if not self.mutexed:
                for row_id in self.row_ids():
                    if self.storage.contains(self.pos(row_id, column_id)):
                        return row_id
                return None
            row = int(self._mutex_vector()[column_id % SHARD_WIDTH])
            return None if row < 0 else row

    def rows_for_columns(self, column_ids):
        """{column_id: row_id} for the given columns via the rows-vector
        (mutex bulk imports)."""
        with self._lock:
            if not self.mutexed:
                # vectorized one-slice_range-per-row scan (no maintained
                # vector on non-mutexed fragments)
                col_by_offset = {int(c) % SHARD_WIDTH: int(c)
                                 for c in column_ids}
                wanted = np.array(sorted(col_by_offset), dtype=np.uint64)
                out = {}
                for row_id in self.row_ids():
                    if len(wanted) == 0:
                        break
                    base = np.uint64(row_id * SHARD_WIDTH)
                    offs = self.storage.slice_range(
                        int(base), int(base) + SHARD_WIDTH) - base
                    mask = np.isin(wanted, offs)
                    if mask.any():
                        for off in wanted[mask]:
                            out[col_by_offset[int(off)]] = row_id
                        wanted = wanted[~mask]
                return out
            vec = self._mutex_vector()
            out = {}
            for c in column_ids:
                row = int(vec[int(c) % SHARD_WIDTH])
                if row >= 0:
                    out[int(c)] = row
            return out

    def contains(self, row_id, column_id):
        with self._lock:
            return self.storage.contains(self.pos(row_id, column_id))

    # -- BSI value ops (reference: fragment.go:896-1000) ---------------------

    def value(self, column_id, bit_depth):
        with self._lock:
            # direct storage probes: contains() would re-acquire the
            # RLock per bit (up to ~66 acquisitions for wide BSI fields)
            def bit(row_id):
                return self.storage.contains(self.pos(row_id, column_id))

            if not bit(BSI_EXISTS_BIT):
                return 0, False
            value = 0
            for i in range(bit_depth):
                if bit(BSI_OFFSET_BIT + i):
                    value |= 1 << i
            if bit(BSI_SIGN_BIT):
                value = -value
            return value, True

    def set_value(self, column_id, bit_depth, value):
        """Sign-magnitude write of base-adjusted value; returns changed."""
        to_set, to_clear = self.positions_for_value(column_id, bit_depth, value)
        return self.import_positions(to_set, to_clear) > 0

    def clear_value(self, column_id, bit_depth):
        to_set, to_clear = self.positions_for_value(
            column_id, bit_depth, 0, clear=True)
        return self.import_positions(to_set, to_clear) > 0

    def positions_for_value(self, column_id, bit_depth, value, clear=False):
        to_set, to_clear = [], []
        uvalue = abs(int(value))
        # existence bit
        (to_clear if clear else to_set).append(self.pos(BSI_EXISTS_BIT, column_id))
        # sign bit
        if value < 0 and not clear:
            to_set.append(self.pos(BSI_SIGN_BIT, column_id))
        else:
            to_clear.append(self.pos(BSI_SIGN_BIT, column_id))
        for i in range(bit_depth):
            p = self.pos(BSI_OFFSET_BIT + i, column_id)
            if (uvalue >> i) & 1:
                to_set.append(p)
            else:
                to_clear.append(p)
        return to_set, to_clear

    # -- bulk ----------------------------------------------------------------

    def import_positions(self, to_set, to_clear):
        """Batched set/clear by raw position (reference: importPositions
        fragment.go:2053). Returns changed count."""
        with self._lock:
            changed = 0
            touched = set()  # rows of the batches that changed a bit
            if len(to_set):
                arr = np.asarray(to_set, dtype=np.uint64)
                n = self.storage.add_many(arr)
                if n:
                    self._append_op(encode_op(OP_ADD_BATCH, values=arr))
                    changed += n
                    touched.update(_rows_of(arr))
            if len(to_clear):
                arr = np.asarray(to_clear, dtype=np.uint64)
                n = self.storage.remove_many(arr)
                if n:
                    self._append_op(encode_op(OP_REMOVE_BATCH, values=arr))
                    changed += n
                    touched.update(_rows_of(arr))
            if changed:
                for row_id in touched:  # before the view's tick moves
                    self._cache_update(row_id)
                self._invalidate_all_rows(touched)
            return changed

    def bulk_import(self, row_ids, column_ids, clear=False):
        """Bulk bit import (reference: bulkImport fragment.go:1997). For
        mutex fragments, each column keeps only its last-written row."""
        row_ids = np.asarray(row_ids, dtype=np.uint64)
        column_ids = np.asarray(column_ids, dtype=np.uint64)
        if self.mutexed and not clear:
            # Clears don't need last-write-wins resolution (reference:
            # bulkImport takes the mutex path only when !options.Clear).
            return self._bulk_import_mutex(row_ids, column_ids)
        positions = row_ids * np.uint64(SHARD_WIDTH) + (
            column_ids % np.uint64(SHARD_WIDTH))
        if clear:
            return self.import_positions([], positions)
        return self.import_positions(positions, [])

    def _bulk_import_mutex(self, row_ids, column_ids):
        with self._lock:
            changed = 0
            # last write per column wins (reference: bulkImportMutex)
            last = {}
            for r, c in zip(row_ids, column_ids):
                last[int(c)] = int(r)
            existing = self.rows_for_columns(list(last))
            vec = self._mutex_vec  # built by rows_for_columns
            to_set, to_clear = [], []
            for c, r in last.items():
                old = existing.get(c)
                if old == r:
                    continue
                if old is not None:
                    to_clear.append(self.pos(old, c))
                to_set.append(self.pos(r, c))
            changed += self.import_positions(to_set, to_clear)
            # import_positions invalidated the vector; the bulk outcome is
            # exactly last-write-wins per column, so patch it back instead
            # of paying a full rebuild on the next mutex write.
            if vec is not None:
                for c, r in last.items():
                    vec[c % SHARD_WIDTH] = r
                self._mutex_vec = vec
            return changed

    def import_roaring(self, data, clear=False):
        """Merge a serialized roaring blob of positions — the fastest ingest
        path (reference: importRoaring fragment.go:2255). Returns changed."""
        other, _, _ = deserialize(data, with_ops=True)
        if os.environ.get("PILOSA_TPU_PARANOIA") == "1":
            other.check()  # reject malformed foreign blobs loudly
        with self._lock:
            changed = merge_bitmaps(self.storage, other, clear=clear)
            if changed:
                op = OP_REMOVE_ROARING if clear else OP_ADD_ROARING
                self._append_op(encode_op(op, roaring=serialize(other), op_n=changed))
                touched = {
                    int(key) // CONTAINERS_PER_SHARD for key in other.keys()}
                for row_id in touched:  # before the view's tick moves
                    self._cache_update(row_id)
                self._invalidate_all_rows(touched)
            return changed

    # -- row planes (the device path) ----------------------------------------

    def row_plane(self, row_id):
        """Host dense words for one row: containers
        [row*CPS, (row+1)*CPS) (reference: rowFromStorage fragment.go:623
        via OffsetRange). Locked: readers must never observe a container
        mid-mutation (the reference guards reads with fragment.mu
        RLock; the stress suite reproduces torn reads without this)."""
        with self._lock:
            return self.storage.dense_range_words(
                row_id * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD)

    def row_device(self, row_id):
        """Device plane for one row, cached until the row is written.

        The device upload happens outside the lock (it can be slow), so
        the cache insert is generation-guarded: a write that lands between
        the snapshot and the insert invalidates the cache slot, and a
        stale plane must not be re-inserted over that invalidation."""
        import jax.numpy as jnp

        cached = self._row_cache.get(row_id)
        if cached is None:
            with self._lock:
                gen = self.generation
                plane = self.storage.dense_range_words(
                    row_id * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD)
            cached = jnp.asarray(plane)
            with self._lock:
                if self.generation == gen:
                    self._row_cache[row_id] = cached
        return cached

    def row_ids(self):
        """Sorted rowIDs with any bit set (reference: fragment.rows),
        memoized per write-generation (mutex set_bit probes this per write).

        The lock-free fast path is a deliberate exception to this file's
        readers-take-the-lock discipline: the (gen, ids) TUPLE is
        published atomically by CPython reference assignment, so a racing
        reader sees either the old pair or the new pair, never a torn
        one; a stale pair fails the generation compare and falls to the
        locked rebuild."""
        cached = self._row_ids_cache
        if cached is not None and cached[0] == self.generation:
            return cached[1]
        with self._lock:
            gen = self.generation
            ids = sorted({
                key // CONTAINERS_PER_SHARD
                for key in self.storage.keys()
                if self.storage.containers[key].n > 0
            })
            self._row_ids_cache = (gen, ids)
        return ids

    def max_row_id(self):
        ids = self.row_ids()
        return ids[-1] if ids else 0

    def row_columns(self, row_id):
        """Absolute column ids of a row (host path, for result assembly)."""
        with self._lock:
            base = row_id * SHARD_WIDTH
            cols = self.storage.slice_range(base, base + SHARD_WIDTH)
        return (cols - np.uint64(base)) + np.uint64(self.shard * SHARD_WIDTH)

    def set_row_plane(self, row_id, plane_words):
        """Overwrite a whole row from dense words (Store/ClearRow writes;
        reference: fragment.setRow fragment.go:760). Returns True when the
        stored row actually changed (bit-exact comparison)."""
        plane_words = np.asarray(plane_words, dtype=np.uint32)
        with self._lock:
            old = self.row_plane(row_id)
            if np.array_equal(old, plane_words):
                return False
            self.storage.replace_dense_words(
                row_id * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD,
                plane_words)
            # WAL: remove whole old row, add new row, as a roaring op pair.
            row_bitmap = Bitmap()
            row_bitmap.replace_dense_words(
                row_id * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD,
                plane_words)
            full = Bitmap()
            full.merge_dense_words(
                row_id * CONTAINERS_PER_SHARD,
                np.full(CONTAINERS_PER_SHARD * WORDS_PER_CONTAINER, 0xFFFFFFFF,
                        dtype=np.uint32))
            self._append_op(encode_op(
                OP_REMOVE_ROARING, roaring=serialize(full), op_n=0))
            self._append_op(encode_op(
                OP_ADD_ROARING, roaring=serialize(row_bitmap), op_n=0))
            self._cache_update(row_id)  # before the view's tick moves
            self._invalidate_row(row_id)
            self._drop_mutex_vec()  # whole-row overwrite: rebuild lazily
            return True

    # -- persistence ---------------------------------------------------------

    def _append_op(self, op_bytes):
        if self._file is not None:
            self._file.write(op_bytes)
            self._file.flush()
            # honor the node-wide fsync policy (one knob for the oplog
            # AND the fragment WAL — the documented durability level is
            # only as strong as its weakest layer)
            oplog_mod.after_append(self._file)
        self.op_n += 1
        if self.op_n > self.max_op_n:
            if self.snapshot_queue is not None:
                if not self._snapshot_pending:
                    self._snapshot_pending = True
                    self.snapshot_queue.enqueue(self)
            else:
                self._snapshot_locked()

    def snapshot(self):
        with self._lock:
            self._snapshot_locked()

    def _snapshot_locked(self):
        """Rewrite the file without the op log (reference:
        unprotectedWriteToFragment fragment.go:2347, temp+rename)."""
        if os.environ.get("PILOSA_TPU_PARANOIA") == "1":
            # paranoid-build analog (reference: roaring_paranoia.go):
            # validate storage invariants before persisting them
            self.storage.check()
        tmp = self.path + ".snapshotting"
        with open(tmp, "wb") as f:
            f.write(serialize(self.storage, flags=self.flags))
            if oplog_mod.fsync_policy() != "never":
                # the rename below atomically replaces snapshot+oplog
                # with snapshot-only; an unsynced temp would make that
                # swap a downgrade on power loss
                oplog_mod.fsync_file(f)
        if self._file:
            self._file.close()
        faultpoints.reached("fragment.snapshot.rename")
        os.replace(tmp, self.path)
        self._file = open(self.path, "ab")
        self.op_n = 0
        self._snapshot_pending = False

    # -- cache/invalidation ---------------------------------------------------

    def row_generation(self, row_id):
        """The generation at which row `row_id` may last have changed: its
        own where a mutator named it, else the floor that mutations of
        unknown extent raise. The invariant serving caches rest on: if
        any bit of the row changed, (uid, row_generation(row)) changed.
        Coarser than the truth is safe, finer never is. Lock-free: one
        attribute read hands over a floor and the rows that go with it."""
        floor, rows = self._row_gens
        return rows.get(row_id, floor)

    def _invalidate_row(self, row_id):
        self._row_cache.pop(row_id, None)
        self._checksums.pop(row_id // HASH_BLOCK_SIZE, None)
        self._note_change((row_id,))

    def _invalidate_all_rows(self, rows=None):
        """A bulk mutation: the per-row host caches go whole (cheap to
        refill); the generations move only for `rows` where the mutator
        knows which it touched — a superset is fine — and for every row
        (the floor) where it does not."""
        self._row_cache.clear()
        self._checksums.clear()
        self._drop_mutex_vec()  # bulk mutation: rebuild lazily
        self._note_change(rows)

    def _note_change(self, rows):
        """Move the fingerprints after storage and the TopN cache changed
        (caller holds self._lock, so writers never race each other here):
        the view keeps unions of both under its tick (View.row_ids,
        View.topn_candidates)."""
        self.generation += 1
        gen = self.generation
        own = self._row_gens[1]
        if rows is None or len(own) + len(rows) > ROW_GENERATIONS_MAX:
            self._row_gens = (gen, {})
        else:
            for row_id in rows:
                own[row_id] = gen
        if self.on_mutate is not None:
            self.on_mutate(rows)

    # -- anti-entropy blocks (reference: Blocks fragment.go:1778) -------------

    def blocks(self):
        """[(block_id, checksum_bytes)] for every 100-row block with bits."""
        out = []
        with self._lock:
            block_ids = sorted({r // HASH_BLOCK_SIZE for r in self.row_ids()})
            for bid in block_ids:
                chk = self._checksums.get(bid)
                if chk is None:
                    positions = self.storage.slice_range(
                        bid * HASH_BLOCK_SIZE * SHARD_WIDTH,
                        (bid + 1) * HASH_BLOCK_SIZE * SHARD_WIDTH)
                    if len(positions) == 0:
                        continue
                    chk = hashlib.blake2b(
                        positions.astype("<u8").tobytes(), digest_size=16).digest()
                    self._checksums[bid] = chk
                out.append((bid, chk))
        return out

    def block_data(self, block_id):
        """(row_ids, column_ids) pairs within a block (reference: blockData)."""
        with self._lock:
            positions = self.storage.slice_range(
                block_id * HASH_BLOCK_SIZE * SHARD_WIDTH,
                (block_id + 1) * HASH_BLOCK_SIZE * SHARD_WIDTH)
        rows = positions // np.uint64(SHARD_WIDTH)
        cols = positions % np.uint64(SHARD_WIDTH)
        return rows, cols

    # -- row counts / cache ---------------------------------------------------

    def row_count(self, row_id):
        """Exact bit count of one row, from container cardinalities —
        row ranges are container-aligned so no densification happens."""
        with self._lock:
            return int(self.storage.count_range(
                row_id * SHARD_WIDTH, (row_id + 1) * SHARD_WIDTH))

    def _cache_update(self, row_id):
        if self.cache is not None:
            self.cache.add(row_id, self.row_count(row_id))

    # -- stats ----------------------------------------------------------------

    def cardinality(self):
        with self._lock:
            return self.storage.count()

    def __repr__(self):
        return (f"<Fragment {self.index}/{self.field}/{self.view}/"
                f"{self.shard} n={self.cardinality()}>")

"""Field: a typed collection of rows (reference: field.go:65).

Types (reference: field.go:56-62): set, int, time, mutex, bool. Options
mirror the reference's functional options (OptFieldType* field.go:127-204):
cache type/size for set fields, min/max/base+bitDepth for int fields, time
quantum (+noStandardView) for time fields.

Metadata persists as JSON in <field>/.meta (the reference uses a protobuf
.meta — internal/private.proto FieldOptions).
"""

import json
import os
import threading

import numpy as np

from . import timeq
from .fragment import (
    BSI_EXISTS_BIT,
    BSI_OFFSET_BIT,
    BSI_SIGN_BIT,
    FALSE_ROW_ID,
    TRUE_ROW_ID,
)
from .view import VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD, ShardList, View

FIELD_TYPE_SET = "set"
FIELD_TYPE_INT = "int"
FIELD_TYPE_TIME = "time"
FIELD_TYPE_MUTEX = "mutex"
FIELD_TYPE_BOOL = "bool"

CACHE_TYPE_RANKED = "ranked"
CACHE_TYPE_LRU = "lru"
CACHE_TYPE_NONE = "none"

DEFAULT_CACHE_TYPE = CACHE_TYPE_RANKED
DEFAULT_CACHE_SIZE = 50_000


class FieldError(Exception):
    pass


def bsi_base(min_value, max_value):
    """Default base offset (reference: bsiBase field.go:1550)."""
    if min_value > 0:
        return min_value
    if max_value < 0:
        return max_value
    return 0


def bit_depth(uvalue):
    return max(int(uvalue).bit_length(), 1)


def bit_depth_range(min_value, max_value, base):
    return max(
        bit_depth(abs(min_value - base)), bit_depth(abs(max_value - base)))


class FieldOptions:
    def __init__(self, type=FIELD_TYPE_SET, cache_type=DEFAULT_CACHE_TYPE,
                 cache_size=DEFAULT_CACHE_SIZE, min=0, max=0, base=None,
                 bit_depth=0, time_quantum="", no_standard_view=False,
                 keys=False):
        self.type = type
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.min = min
        self.max = max
        self.base = bsi_base(min, max) if base is None else base
        self.bit_depth = bit_depth
        self.time_quantum = time_quantum
        self.no_standard_view = no_standard_view
        self.keys = keys

    def to_dict(self):
        return dict(self.__dict__)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)

    @classmethod
    def int_field(cls, min=-(1 << 31), max=(1 << 31) - 1):
        base = bsi_base(min, max)
        return cls(type=FIELD_TYPE_INT, min=min, max=max, base=base,
                   bit_depth=bit_depth_range(min, max, base),
                   cache_type=CACHE_TYPE_NONE, cache_size=0)

    @classmethod
    def time_field(cls, quantum, no_standard_view=False, keys=False):
        timeq.validate_quantum(quantum)
        return cls(type=FIELD_TYPE_TIME, time_quantum=quantum,
                   no_standard_view=no_standard_view,
                   cache_type=CACHE_TYPE_NONE, cache_size=0, keys=keys)

    @classmethod
    def mutex_field(cls, cache_type=DEFAULT_CACHE_TYPE,
                    cache_size=DEFAULT_CACHE_SIZE, keys=False):
        return cls(type=FIELD_TYPE_MUTEX, cache_type=cache_type,
                   cache_size=cache_size, keys=keys)

    @classmethod
    def bool_field(cls):
        return cls(type=FIELD_TYPE_BOOL, cache_type=CACHE_TYPE_NONE,
                   cache_size=0)


class Field(ShardList):
    def __init__(self, path, index_name, name, options=None,
                 max_op_n=None, snapshot_queue=None, row_attr_store=None,
                 translate_configurer=None):
        self.path = path
        self.index_name = index_name
        self.name = name
        self.options = options or FieldOptions()
        self.max_op_n = max_op_n
        self.snapshot_queue = snapshot_queue
        self.views = {}  # name -> View
        self.row_attr_store = row_attr_store
        self.translate_store = None  # row key translation when keys=True
        self.translate_configurer = translate_configurer
        self._lock = threading.RLock()

    # -- lifecycle ----------------------------------------------------------

    @property
    def meta_path(self):
        return os.path.join(self.path, ".meta")

    def open(self):
        from ..storage import SqliteAttrStore, SqliteTranslateStore

        os.makedirs(self.path, exist_ok=True)
        if os.path.exists(self.meta_path):
            with open(self.meta_path) as f:
                self.options = FieldOptions.from_dict(json.load(f))
        else:
            self.save_meta()
        if self.row_attr_store is None:
            self.row_attr_store = SqliteAttrStore(
                os.path.join(self.path, ".attrs.db"))
        if self.options.keys and self.translate_store is None:
            self.translate_store = SqliteTranslateStore(
                os.path.join(self.path, ".keys.db"),
                index=self.index_name, field=self.name)
            if self.translate_configurer is not None:
                self.translate_configurer(self.translate_store)
        views_dir = os.path.join(self.path, "views")
        if os.path.isdir(views_dir):
            for name in sorted(os.listdir(views_dir)):
                self._new_view(name).open()
        return self

    def save_meta(self):
        os.makedirs(self.path, exist_ok=True)
        with open(self.meta_path, "w") as f:
            json.dump(self.options.to_dict(), f)

    def close(self):
        with self._lock:
            for v in self.views.values():
                v.close()
            self.views.clear()
            self.structure_changed()
            if self.row_attr_store is not None:
                self.row_attr_store.close()
                self.row_attr_store = None
            if self.translate_store is not None:
                self.translate_store.close()
                self.translate_store = None

    # -- views --------------------------------------------------------------

    def _new_view(self, name):
        view = View(
            os.path.join(self.path, "views", name), self.index_name,
            self.name, name, max_op_n=self.max_op_n,
            snapshot_queue=self.snapshot_queue,
            mutexed=self.options.type in (FIELD_TYPE_MUTEX, FIELD_TYPE_BOOL),
            cache_type=self.options.cache_type,
            cache_size=self.options.cache_size)
        view.on_structure = self.structure_changed
        self.views[name] = view
        self.structure_changed()
        return view

    def view(self, name=VIEW_STANDARD):
        return self.views.get(name)

    def create_view_if_not_exists(self, name):
        with self._lock:
            view = self.views.get(name)
            if view is None:
                view = self._new_view(name)
                view.open()
            return view

    def bsi_view_name(self):
        return VIEW_BSI_GROUP_PREFIX + self.name

    @property
    def type(self):
        return self.options.type

    def time_quantum(self):
        return self.options.time_quantum

    def _shard_children(self):
        return self.views

    # -- bit ops ------------------------------------------------------------

    def set_bit(self, row_id, column_id, timestamp=None):
        """(reference: Field.SetBit field.go:927)"""
        if self.type == FIELD_TYPE_INT:
            raise FieldError(f"set_bit unsupported for field type {self.type}")
        changed = False
        if not self.options.no_standard_view:
            changed |= self.create_view_if_not_exists(VIEW_STANDARD).set_bit(
                row_id, column_id)
        if timestamp is not None:
            if self.type != FIELD_TYPE_TIME:
                raise FieldError(
                    f"cannot set timestamp on {self.type} field")
            for name in timeq.views_by_time(
                    VIEW_STANDARD, timestamp, self.time_quantum()):
                changed |= self.create_view_if_not_exists(name).set_bit(
                    row_id, column_id)
        return changed

    def clear_bit(self, row_id, column_id):
        if self.type == FIELD_TYPE_INT:
            raise FieldError(f"clear_bit unsupported for field type {self.type}")
        changed = False
        for name, view in list(self.views.items()):
            if name.startswith(VIEW_BSI_GROUP_PREFIX):
                continue
            changed |= view.clear_bit(row_id, column_id)
        return changed

    # -- BSI value ops ------------------------------------------------------

    def _require_int(self):
        if self.type != FIELD_TYPE_INT:
            raise FieldError(f"bsiGroup not found on field type {self.type}")

    def set_value(self, column_id, value):
        """(reference: Field.SetValue field.go:1075) value stored
        base-adjusted sign-magnitude; grows bitDepth on demand."""
        self._require_int()
        opts = self.options
        value = int(value)
        if value < opts.min:
            raise FieldError(f"value {value} below field minimum {opts.min}")
        if value > opts.max:
            raise FieldError(f"value {value} above field maximum {opts.max}")
        base_value = value - opts.base
        required = bit_depth(abs(base_value))
        if required > opts.bit_depth:
            opts.bit_depth = required
            self.save_meta()
        view = self.create_view_if_not_exists(self.bsi_view_name())
        return view.set_value(column_id, opts.bit_depth, base_value)

    def clear_value(self, column_id):
        self._require_int()
        view = self.view(self.bsi_view_name())
        if view is None:
            return False
        return view.clear_value(column_id, self.options.bit_depth)

    def value(self, column_id):
        self._require_int()
        view = self.view(self.bsi_view_name())
        if view is None:
            return 0, False
        v, exists = view.value(column_id, self.options.bit_depth)
        return (v + self.options.base, True) if exists else (0, False)

    # -- bool convenience ---------------------------------------------------

    def set_bool(self, column_id, value):
        return self.set_bit(TRUE_ROW_ID if value else FALSE_ROW_ID, column_id)

    # -- bulk import --------------------------------------------------------

    def import_bits(self, row_ids, column_ids, timestamps=None, clear=False):
        """Bulk import grouped by shard (reference: Field.Import
        field.go:1204). Timestamps fan rows out to quantum views."""
        from ..shardwidth import SHARD_WIDTH

        row_ids = np.asarray(row_ids, dtype=np.uint64)
        column_ids = np.asarray(column_ids, dtype=np.uint64)
        if len(row_ids) != len(column_ids):
            raise FieldError("mismatched row/column lengths")

        # view name -> (rows, cols) selections
        work = {}
        if timestamps is None:
            work[VIEW_STANDARD] = (row_ids, column_ids)
        else:
            if self.type != FIELD_TYPE_TIME:
                raise FieldError("timestamps on non-time field")
            by_view = {}
            for i, ts in enumerate(timestamps):
                if ts is None:
                    # Untimed bits always land in the standard view, even
                    # under no_standard_view (reference: Field.Import routes
                    # zero-timestamp bits to viewStandard, field.go:1242).
                    by_view.setdefault(VIEW_STANDARD, []).append(i)
                    continue
                for name in timeq.views_by_time(
                        VIEW_STANDARD, ts, self.time_quantum()):
                    by_view.setdefault(name, []).append(i)
            if not self.options.no_standard_view:
                work[VIEW_STANDARD] = (row_ids, column_ids)
                by_view.pop(VIEW_STANDARD, None)
            for name, idxs in by_view.items():
                idxs = np.asarray(idxs, dtype=np.int64)
                work[name] = (row_ids[idxs], column_ids[idxs])

        changed = 0
        for name, (rows, cols) in work.items():
            view = self.create_view_if_not_exists(name)
            shards = cols // np.uint64(SHARD_WIDTH)
            for shard in np.unique(shards):
                sel = shards == shard
                frag = view.create_fragment_if_not_exists(int(shard))
                changed += frag.bulk_import(rows[sel], cols[sel], clear=clear)
        return changed

    def import_values(self, column_ids, values, clear=False):
        """Bulk BSI import (reference: Field.importValue field.go:1285).
        clear=True removes the stored value of every listed column (the
        values are ignored; reference: fragment.importValue's clear arg
        fragment.go:2205)."""
        from ..shardwidth import SHARD_WIDTH

        self._require_int()
        if clear:
            changed = 0
            for col in np.asarray(column_ids, dtype=np.uint64).tolist():
                changed += bool(self.clear_value(int(col)))
            return changed
        opts = self.options
        column_ids = np.asarray(column_ids, dtype=np.uint64)
        values = np.asarray(values, dtype=np.int64)
        if len(values) and (values.min() < opts.min or values.max() > opts.max):
            raise FieldError("value out of range for field")
        base_values = values - opts.base
        if len(values):
            required = bit_depth(int(np.abs(base_values).max()))
            if required > opts.bit_depth:
                opts.bit_depth = required
                self.save_meta()
        view = self.create_view_if_not_exists(self.bsi_view_name())
        shards = column_ids // np.uint64(SHARD_WIDTH)
        changed = 0
        for shard in np.unique(shards):
            sel = shards == shard
            frag = view.create_fragment_if_not_exists(int(shard))
            to_set, to_clear = [], []
            for col, bval in zip(column_ids[sel], base_values[sel]):
                s, c = frag.positions_for_value(
                    int(col), opts.bit_depth, int(bval))
                to_set.extend(s)
                to_clear.extend(c)
            changed += frag.import_positions(to_set, to_clear)
        return changed

"""Index: a namespace of fields over a shared column space.

Reference: index.go:37. Holds fields, column attributes, the optional
`_exists` existence field used by Not() queries (track_existence;
reference: index.go:215, holder.go:46), and the column-keys option.
"""

import json
import os
import re
import threading
import time

from .field import Field, FieldOptions
from .view import ShardList

EXISTENCE_FIELD_NAME = "_exists"  # reference: holder.go:46

# Rebuilds of an index's kept shard list and the walks' own seconds, over
# every index of the process (/debug/vars holder). Counted on a rebuild
# only, so a hit pays nothing; the levels below rebuild inside the walk.
shard_list_stats = {"shard_list_rebuilds": 0, "shard_list_seconds": 0.0}

_NAME_RE = re.compile(r"^[a-z][a-z0-9_-]{0,63}$")  # reference: pilosa.go:121


class IndexError_(Exception):
    pass


def validate_name(name):
    if not _NAME_RE.match(name):
        raise IndexError_(
            f"invalid name {name!r}: must match [a-z][a-z0-9_-]{{0,63}}")
    return name


class IndexOptions:
    def __init__(self, keys=False, track_existence=True):
        self.keys = keys
        self.track_existence = track_existence

    def to_dict(self):
        return dict(self.__dict__)

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


class Index(ShardList):
    def __init__(self, path, name, options=None, max_op_n=None,
                 snapshot_queue=None, column_attr_store=None,
                 row_attr_stores=None, translate_configurer=None):
        self.path = path
        self.name = name
        self.options = options or IndexOptions()
        self.max_op_n = max_op_n
        self.snapshot_queue = snapshot_queue
        self.fields = {}
        self.column_attr_store = column_attr_store
        self.translate_store = None  # column key translation when keys=True
        # called with each new translate store (replication wiring: sets
        # read-only + the remote-create hook before any write can race)
        self.translate_configurer = translate_configurer
        self._row_attr_stores = row_attr_stores or {}
        self._lock = threading.RLock()

    @property
    def meta_path(self):
        return os.path.join(self.path, ".meta")

    @property
    def keys(self):
        return self.options.keys

    def open(self):
        from ..storage import SqliteAttrStore, SqliteTranslateStore

        os.makedirs(self.path, exist_ok=True)
        if os.path.exists(self.meta_path):
            with open(self.meta_path) as f:
                self.options = IndexOptions.from_dict(json.load(f))
        else:
            self.save_meta()
        if self.column_attr_store is None:
            self.column_attr_store = SqliteAttrStore(
                os.path.join(self.path, ".attrs.db"))
        if self.options.keys and self.translate_store is None:
            self.translate_store = SqliteTranslateStore(
                os.path.join(self.path, ".keys.db"), index=self.name)
            if self.translate_configurer is not None:
                self.translate_configurer(self.translate_store)
        for name in sorted(os.listdir(self.path)):
            sub = os.path.join(self.path, name)
            if os.path.isdir(sub) and os.path.exists(os.path.join(sub, ".meta")):
                self._new_field(name).open()
        if self.options.track_existence and EXISTENCE_FIELD_NAME not in self.fields:
            self._create_existence_field()
        return self

    def save_meta(self):
        os.makedirs(self.path, exist_ok=True)
        with open(self.meta_path, "w") as f:
            json.dump(self.options.to_dict(), f)

    def close(self):
        with self._lock:
            for f in self.fields.values():
                f.close()
            self.fields.clear()
            self.structure_changed()
            if self.column_attr_store is not None:
                self.column_attr_store.close()
                self.column_attr_store = None
            if self.translate_store is not None:
                self.translate_store.close()
                self.translate_store = None

    # -- fields -------------------------------------------------------------

    def _new_field(self, name, options=None):
        field = Field(
            os.path.join(self.path, name), self.name, name, options=options,
            max_op_n=self.max_op_n, snapshot_queue=self.snapshot_queue,
            row_attr_store=self._row_attr_stores.get(name),
            translate_configurer=self.translate_configurer)
        field.on_structure = self.structure_changed
        self.fields[name] = field
        self.structure_changed()
        return field

    def _create_existence_field(self):
        field = self._new_field(EXISTENCE_FIELD_NAME, FieldOptions(
            cache_type="none", cache_size=0))
        field.open()
        return field

    def field(self, name):
        return self.fields.get(name)

    def existence_field(self):
        return self.fields.get(EXISTENCE_FIELD_NAME)

    def create_field(self, name, options=None, if_not_exists=False):
        """(reference: Index.CreateField index.go:351)"""
        validate_name(name)
        with self._lock:
            existing = self.fields.get(name)
            if existing is not None:
                if if_not_exists:
                    return existing
                raise IndexError_(f"field already exists: {name}")
            field = self._new_field(name, options or FieldOptions())
            field.open()
            return field

    def delete_field(self, name):
        import shutil

        with self._lock:
            field = self.fields.pop(name, None)
            if field is None:
                raise IndexError_(f"field not found: {name}")
            self.structure_changed()
            field.close()
            shutil.rmtree(field.path, ignore_errors=True)

    def public_fields(self):
        return {n: f for n, f in self.fields.items()
                if n != EXISTENCE_FIELD_NAME}

    # -- shards -------------------------------------------------------------

    def _shard_children(self):
        return self.fields

    def _walk_shards(self):
        """(reference: Index.AvailableShards index.go:292)"""
        t0 = time.perf_counter()
        shards = super()._walk_shards()
        shard_list_stats["shard_list_rebuilds"] += 1
        shard_list_stats["shard_list_seconds"] += time.perf_counter() - t0
        return shards

    # -- existence tracking --------------------------------------------------

    def add_existence(self, column_ids):
        if not self.options.track_existence:
            return
        field = self.existence_field()
        if field is None:
            field = self._create_existence_field()
        import numpy as np

        column_ids = np.asarray(column_ids, dtype=np.uint64)
        field.import_bits(np.zeros(len(column_ids), dtype=np.uint64), column_ids)

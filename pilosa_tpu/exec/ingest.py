"""Streaming ingest engine: delta-buffered writes with interval-batched,
donated device scatter-merges (ROADMAP item 4, "Production write path").

The problem: PR 7 made acked writes durable, but stack maintenance still
happened on the READ path — every import bumps fragment generations, and
the next query over a stale cached stack repairs it inline (host gather +
device patch dispatch under the process-wide dispatch lock), while
compressed containers decay to dense on the first write. Sustained
ingest therefore taxes read p99 once per (fragment, interval) — the
reference never pays this because roaring absorbs write churn in an
op-log-over-snapshot delta (roaring.go:228-249); this module is the
device analogue.

Shape:

  server/api.py import paths      exec/ingest.py merge thread
  ------------------------------  ---------------------------------
  oplog append  (durability)      every --ingest-merge-interval, or
  fragment apply (host truth)       at the rows/bytes high-water mark:
  record() -> delta buffer        drain: ONE batched scatter-merge
  ack (unchanged)                   dispatch folds all pending deltas
                                    into the touched resident stacks
                                    (jax.jit, donated stack buffers)

Reads whose cache-entry drift is FULLY covered by pending deltas serve
the resident stack as-is (bounded staleness <= one merge interval; see
covers_pending). Drift the buffer does not cover — a PQL Set/Clear on a
fragment with no pending entry, a dropped/recreated fragment — falls
back to the legacy read-path repair unchanged. Interval 0 (the default)
never constructs an engine: the import path is one `is None` check and
every read behaves byte-identically to the legacy per-import
invalidation.

Crash semantics: buffered-but-unmerged deltas are ALREADY durable — the
oplog record precedes the buffer append, and the host fragments hold the
applied bits. Only the device stack cache is behind; a crash loses
nothing and boot replay needs no new machinery. Under fsync=interval the
engine also group-commits the applied watermark: mark_applied calls for
acked imports batch per merge interval (bounded by the oplog's existing
gap set), flushed at every drain and at close().

Donation lifecycle: the merge scatter donates the resident stack buffer
(update-in-place on TPU — no second copy of a 512 MB pool at peak; the
CPU backend ignores donation and copies). The dispatch runs under the
process-wide dispatch lock, so no serving launch interleaves with it; a
reader that grabbed the OLD container right before the merge and
dispatches after it will see a donated-buffer error on TPU — the window
is one lock handoff wide and retries resolve it, but it is why merges
swap entries only after the barrier, never mid-flight.
"""

import threading
import time
import warnings

import numpy as np

from ..utils import faultpoints
from ..utils import flightrec as _flightrec
from ..utils import tracing as _tracing
from ..utils.stats import global_stats

__all__ = [
    "IngestEngine",
    "covers_pending",
    "mode",
    "DEFAULT_MAX_ROWS",
    "DEFAULT_MAX_BYTES",
]

#: high-water marks that force an early drain (and 503 back-pressure
#: past them): enough headroom for seconds of bulk import without
#: letting an unmerged backlog grow unboundedly between intervals
DEFAULT_MAX_ROWS = 1_000_000
DEFAULT_MAX_BYTES = 64 << 20

# jax warns once per donated jit on backends that ignore donation (the
# CPU test backend); the fallback is exactly the legacy copying scatter,
# so the warning is noise here
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

_REGISTRY_LOCK = threading.Lock()
_REGISTRY = []  # active engines; read lock-free on the serving path


def covers_pending(index, field, view, shards, old_gens, gens):
    """True when EVERY drifted shard of a stale cache entry is covered
    by a pending ingest delta at its current generation — the read may
    serve the resident stack as-is and leave the fold to the interval
    merge. One list check when no engine is active (the default)."""
    engines = _REGISTRY
    if not engines:
        return False
    for eng in engines:
        if eng.covers(index, field, view, shards, old_gens, gens):
            return True
    return False


def mode():
    """'off' or 'interval=<seconds>s' — bench attempt tagging."""
    engines = _REGISTRY
    if not engines:
        return "off"
    return f"interval={engines[0].interval:g}s"


def _build_scatter_axis0():
    import jax

    return jax.jit(lambda stack, jdx, block: stack.at[jdx].set(block),
                   donate_argnums=(0,))


def _build_scatter_axis1():
    import jax

    return jax.jit(lambda stack, jdx, block: stack.at[:, jdx].set(block),
                   donate_argnums=(0,))


def _build_scatter_bsi():
    import jax

    def scatter(planes, sign, exists, jdx, block):
        return (planes.at[:, jdx].set(block[2:]),
                sign.at[jdx].set(block[1]),
                exists.at[jdx].set(block[0]))

    return jax.jit(scatter, donate_argnums=(0, 1, 2))


class IngestEngine:
    """Bounded host-side delta buffer + background interval merger for
    one API's local evaluator. Construct only with interval > 0; the
    thread starts immediately and close() drains the tail."""

    def __init__(self, api, interval, max_rows=None, max_bytes=None):
        if interval <= 0:
            raise ValueError("ingest merge interval must be > 0")
        self.api = api
        self.interval = float(interval)
        self.max_rows = int(max_rows or DEFAULT_MAX_ROWS)
        self.max_bytes = int(max_bytes or DEFAULT_MAX_BYTES)
        # pending: (index, field, view, shard) -> [uid, gen, rows, bytes]
        # — the (uid, gen) is the fragment's generation AFTER the
        # recorded apply, which is what covers() compares reads against
        self._pending = {}
        self._rows = 0
        self._bytes = 0
        self._deferred = []  # lsns whose mark_applied group-commits
        self._plock = threading.Lock()
        self._merge_lock = threading.Lock()  # serializes drains
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._closed = False
        # counters (under _plock; ints, so snapshots are cheap)
        self.merges = 0
        self.merged_keys = 0
        self.scatter_entries = 0
        self.overlay_entries = 0
        self.rebuilt_entries = 0
        self.dropped_entries = 0
        self.overflows = 0
        self.merges_shed = 0
        self.group_commit_flushed = 0
        self.last_merge = None  # {wall_seconds, at, entries, deltas}
        # admission-ladder hook: when set and truthy at a TIMER tick,
        # the interval merge is skipped (deltas keep buffering; reads
        # serve the resident — stale — stacks). Overflow wakes always
        # merge: shedding those would deadlock the write path behind
        # its own back-pressure gate.
        self._shed_probe = None
        with _REGISTRY_LOCK:
            _REGISTRY.append(self)
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="ingest-merge")
        self._thread.start()

    # -- write-path hooks (called by server/api.py) ---------------------------

    def set_shed_probe(self, fn):
        """Install the admission ladder's merge-shed predicate (called
        once at API construction; None clears)."""
        self._shed_probe = fn

    def admit(self, rows, nbytes):
        """Back-pressure gate BEFORE the oplog append: returns a
        retry-after in seconds when the buffer is past its high-water
        mark (the API turns it into 503 + Retry-After), else None. An
        overflow also wakes the merger immediately."""
        with self._plock:
            over = (self._rows + rows > self.max_rows
                    or self._bytes + nbytes > self.max_bytes)
            if over:
                self.overflows += 1
        if over:
            _flightrec.record("ingest.overflow", rows=self._rows,
                              bytes=self._bytes)
            global_stats.count("ingest_overflows", 1)
            self._wake.set()
            return max(1.0, self.interval)
        return None

    def record(self, index_name, field, shard_rows, nbytes):
        """Buffer one applied import's deltas: for every view of `field`
        and every touched shard, remember the fragment's post-apply
        (uid, generation). The merge gathers planes from the
        authoritative host fragments, so recording the CURRENT gens is
        exact — any earlier un-recorded write to the same fragment rides
        the same fold. `shard_rows` maps shard -> input rows landed
        there; `nbytes` is the import's wire-size estimate (distributed
        per shard for the high-water accounting)."""
        if not shard_rows:
            return
        total = sum(shard_rows.values()) or 1
        entries = []
        for view in list(field.views.values()):
            for shard, n in shard_rows.items():
                frag = view.fragment(shard)
                if frag is None:
                    continue
                entries.append(
                    ((index_name, field.name, view.name, shard),
                     frag.uid, frag.generation, n,
                     nbytes * n // total))
        if not entries:
            return
        high = False
        with self._plock:
            for key, uid, gen, n, nb in entries:
                rec = self._pending.get(key)
                if rec is not None and (rec[0], rec[1]) == (uid, gen):
                    rec[2] += n
                    rec[3] += nb
                else:
                    prev_rows = rec[2] if rec is not None else 0
                    prev_bytes = rec[3] if rec is not None else 0
                    self._pending[key] = [uid, gen, prev_rows + n,
                                          prev_bytes + nb]
                self._rows += n
                self._bytes += nb
            high = (self._rows >= self.max_rows
                    or self._bytes >= self.max_bytes)
        if high:
            self._wake.set()

    def defer_applied(self, lsn):
        """Group-commit hook: True = this record's mark_applied is
        deferred to the next drain (fsync=interval only — under
        fsync=always the watermark IS the durability contract and
        advances per record as before)."""
        if lsn is None or self._closed:
            return False
        oplog = self.api.oplog
        if oplog is None or oplog.fsync != "interval":
            return False
        with self._plock:
            if self._closed:
                return False
            self._deferred.append(lsn)
        return True

    def covers(self, index, field, view, shards, old_gens, gens):
        """True when every drifted shard's newest change is no newer than
        a pending delta record of the same fragment — i.e. the merge will
        fold the drift this read sees. `gens` are fragment-wide
        generations (read now, so never under the record's: equal or not
        covered) or, for a leaf stack, its row's generations (the row
        last changed at or before the recorded write). Anything newer
        than the record — a write that left none — is not covered and
        falls to the read path's exact patch."""
        hit = False
        with self._plock:
            pending = self._pending
            for j, (o, n) in enumerate(zip(old_gens, gens)):
                if o == n:
                    continue
                rec = pending.get((index, field, view, shards[j]))
                if rec is None or rec[0] != n[0] or n[1] > rec[1]:
                    return False
                hit = True
        return hit

    # -- merge ---------------------------------------------------------------

    def _evaluator(self):
        ex = getattr(self.api.executor, "local", self.api.executor)
        return getattr(ex, "_stacked", None)

    def flush(self):
        """Synchronous drain (tests; close). Serialized with the
        background thread's drains."""
        with self._merge_lock:
            self._drain()

    def _loop(self):
        while True:
            forced = self._wake.wait(self.interval)
            self._wake.clear()
            if self._stop.is_set():
                return
            probe = self._shed_probe
            if not forced and probe is not None and probe():
                # SHED_BATCH+: skip the interval merge to keep the
                # device free for interactive reads. Deltas stay
                # buffered; an overflow (forced wake) still merges.
                with self._plock:
                    self.merges_shed += 1
                    pending = bool(self._pending or self._deferred)
                if pending:
                    _flightrec.record("ingest.merge_shed",
                                      rows=self._rows, bytes=self._bytes)
                continue
            try:
                with _tracing.annotate("ingest.merge"):
                    self.flush()
            except Exception as exc:  # noqa: BLE001 — keep merging
                global_stats.count("ingest_merge_errors", 1)
                _flightrec.record("ingest.merge_error", error=str(exc))

    def _drain(self):
        with self._plock:
            snapshot = dict(self._pending)
            deferred = self._deferred
            self._deferred = []
        if not snapshot and not deferred:
            return
        faultpoints.reached("ingest.pre-merge")
        t0 = time.perf_counter()
        stats = {"entries": 0, "scatters": 0, "overlays": 0,
                 "rebuilds": 0, "drops": 0}
        if snapshot:
            touched = {(k[0], k[1]) for k in snapshot}
            ev = self._evaluator()
            if ev is not None:
                self._merge_into(ev, touched, stats)
        if deferred:
            for lsn in deferred:
                self.api._oplog_applied(lsn)
            global_stats.timing("oplog_group_commit_records",
                                float(len(deferred)))
        # retire folded keys: a record() that landed mid-merge replaced
        # the key's value object, so the identity compare keeps it for
        # the next interval (its write IS newer than the gathered plane)
        with self._plock:
            for k, v in snapshot.items():
                if self._pending.get(k) is v:
                    del self._pending[k]
                    self._rows -= v[2]
                    self._bytes -= v[3]
            if not self._pending:
                self._rows = 0
                self._bytes = 0
            self.merges += 1
            self.merged_keys += len(snapshot)
            self.scatter_entries += stats["scatters"]
            self.overlay_entries += stats["overlays"]
            self.rebuilt_entries += stats["rebuilds"]
            self.dropped_entries += stats["drops"]
            self.group_commit_flushed += len(deferred)
            wall = time.perf_counter() - t0
            self.last_merge = {
                "wall_seconds": round(wall, 6),
                "at": time.time(),
                "entries": stats["entries"],
                "deltas": len(snapshot),
                "group_commit_records": len(deferred),
            }
        global_stats.timing("ingest_merge_seconds", wall)
        _flightrec.record(
            "ingest.merge", deltas=len(snapshot),
            entries=stats["entries"], scatters=stats["scatters"],
            overlays=stats["overlays"], rebuilds=stats["rebuilds"],
            drops=stats["drops"], group_commit=len(deferred),
            wall_seconds=round(wall, 6))

    def _merge_into(self, ev, touched, stats):
        """Fold pending deltas into every touched resident stack: plan +
        host-gather outside any lock, then ONE dispatch-lock window for
        all donated scatters, then swap entries in. Entries too drifted
        to patch drop (the next read rebuilds cold — a build, not a
        read-path patch); compressed containers take an overlay term or
        a full rebuild with the repr re-chosen."""
        import jax.numpy as jnp

        from ..core.fragment import (
            BSI_EXISTS_BIT,
            BSI_OFFSET_BIT,
            BSI_SIGN_BIT,
        )
        from ..core.view import VIEW_STANDARD
        from ..ops import containers as _containers
        from . import stacked as _stacked

        holder = self.api.holder
        with ev._lock:
            items = list(ev._stacks.items()) + list(ev._rows_stacks.items())
        scatters = []
        for key, entry in items:
            if (key[1], key[2]) not in touched:
                continue
            kind = key[0]
            idx = holder.index(key[1])
            field = idx.field(key[2]) if idx is not None else None
            if field is None:
                if ev.merge_drop(key, entry):
                    stats["drops"] += 1
                continue
            if kind == "leaf":
                view_name, shards, rows = VIEW_STANDARD, key[4], [key[3]]
            elif kind == "rows":
                view_name, shards, rows = key[3], key[5], list(key[4])
            elif kind == "bsi":
                view_name = field.bsi_view_name()
                shards = key[4]
                rows = [BSI_EXISTS_BIT, BSI_SIGN_BIT] + [
                    BSI_OFFSET_BIT + i for i in range(key[3])]
            else:
                continue
            view = field.view(view_name)
            if view is None:
                if ev.merge_drop(key, entry):
                    stats["drops"] += 1
                continue
            gens = ev._fragment_gens(
                idx, key[2], shards, view_name, view=view,
                row_id=key[3] if kind == "leaf" else None)
            old_gens = entry[0]
            if gens is None or len(old_gens) != len(gens):
                if ev.merge_drop(key, entry):
                    stats["drops"] += 1
                continue
            if old_gens == gens:
                continue  # already current
            changed = [j for j, (o, n) in enumerate(zip(old_gens, gens))
                       if o != n]
            ent = entry[1]
            stats["entries"] += 1
            if (kind == "leaf" and isinstance(ent, _containers.Container)
                    and ent.kind != "dense"):
                self._merge_compressed(ev, key, entry, ent, gens, view,
                                       shards, changed, stats,
                                       _containers, VIEW_STANDARD)
                continue
            if len(changed) * 2 > len(shards):
                # past the patch cutoff a merge-time fold would re-upload
                # most of the stack anyway — drop and let demand rebuild
                if ev.merge_drop(key, entry):
                    stats["drops"] += 1
                continue
            block = ev._host_rows(view, rows,
                                  [shards[j] for j in changed], pad=False)
            scatters.append((kind, key, entry, gens,
                             np.asarray(changed), block))
        if not scatters:
            return
        nbytes_in = sum(p[5].nbytes for p in scatters)
        outs = []
        with ev._locked_dispatch("ingest_merge", nbytes_in=nbytes_in) as ph:
            for kind, key, entry, gens, jdx, block in scatters:
                ent = entry[1]
                if kind == "leaf":
                    fn = ev._get_fn(("ingest_scatter", 0),
                                    _build_scatter_axis0)
                    stack = (ent.arrays[0]
                             if isinstance(ent, _containers.Container)
                             else ent)
                    outs.append(fn(stack, jnp.asarray(jdx),
                                   jnp.asarray(block[0])))
                elif kind == "rows":
                    fn = ev._get_fn(("ingest_scatter", 1),
                                    _build_scatter_axis1)
                    outs.append(fn(ent, jnp.asarray(jdx),
                                   jnp.asarray(block)))
                else:
                    fn = ev._get_fn(("ingest_scatter", "bsi"),
                                    _build_scatter_bsi)
                    planes, sign, exists = ent
                    outs.append(fn(planes, sign, exists,
                                   jnp.asarray(jdx), jnp.asarray(block)))
            ph.mark("dispatch_ack")
            for out in outs:
                _stacked._launch_barrier(out)
            ph.mark("sync")
        for (kind, key, entry, gens, jdx, block), out in zip(scatters,
                                                             outs):
            if kind == "leaf":
                cont = _containers.dense_container(out)
                ok = ev.merge_swap(key, entry, gens, cont, cont.nbytes)
            elif kind == "rows":
                ok = ev.merge_swap(key, entry, gens, out,
                                   int(out.size) * 4)
            else:
                ok = ev.merge_swap(key, entry, gens, tuple(out), entry[2])
            if ok:
                stats["scatters"] += 1

    def _merge_compressed(self, ev, key, entry, ent, gens, view, shards,
                          changed, stats, _containers, view_standard):
        """Compressed leaf: park the drifted planes as an overlay term
        beside the sparse/rle base, or — past the overlay budget — do a
        full rebuild with the representation re-chosen from the measured
        density (the interval is where repr churn is allowed)."""
        over_budget = (
            ent.overlay + 1 > _containers.OVERLAY_MAX_TERMS
            or (_containers.overlay_rows(ent) + len(changed)
                > max(1, len(shards) // 2)))
        if over_budget:
            host = ev._host_rows(view, [key[3]], shards)
            cont = _containers.build(
                host[0],
                place_sharded=lambda a: ev._place(a, shard_axis=0),
                place_replicated=ev._place_replicated,
                fragment=(key[1], key[2], view_standard, key[3]))
            if ev.merge_swap(key, entry, gens, cont, cont.nbytes):
                stats["rebuilds"] += 1
            return
        block = ev._host_rows(view, [key[3]],
                              [shards[j] for j in changed], pad=False)
        cont = _containers.with_overlay(
            ent, ev._place_replicated,
            np.asarray(changed, np.int32), block[0])
        if ev.merge_swap(key, entry, gens, cont, cont.nbytes):
            stats["overlays"] += 1

    # -- observability / lifecycle -------------------------------------------

    def snapshot(self):
        """GET /debug/ingest payload."""
        with self._plock:
            per_field = {}
            for (index, field, _view, _shard), v in self._pending.items():
                e = per_field.setdefault(
                    f"{index}/{field}",
                    {"deltas": 0, "rows": 0, "bytes": 0})
                e["deltas"] += 1
                e["rows"] += v[2]
                e["bytes"] += v[3]
            last = dict(self.last_merge) if self.last_merge else None
            out = {
                "enabled": True,
                "interval_seconds": self.interval,
                "max_rows": self.max_rows,
                "max_bytes": self.max_bytes,
                "pending": {
                    "entries": len(self._pending),
                    "rows": self._rows,
                    "bytes": self._bytes,
                    "deferred_lsns": len(self._deferred),
                },
                "per_field": per_field,
                "merges": self.merges,
                "merged_keys": self.merged_keys,
                "scatter_entries": self.scatter_entries,
                "overlay_entries": self.overlay_entries,
                "rebuilt_entries": self.rebuilt_entries,
                "dropped_entries": self.dropped_entries,
                "overflows": self.overflows,
                "merges_shed": self.merges_shed,
                "group_commit_flushed": self.group_commit_flushed,
                "last_merge": last,
            }
        if last is not None:
            out["last_merge"]["age_seconds"] = round(
                time.time() - last["at"], 3)
        return out

    def close(self):
        """Stop the merger and drain the tail (pending deltas fold,
        deferred watermarks flush). Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=10)
        self.flush()
        with _REGISTRY_LOCK:
            try:
                _REGISTRY.remove(self)
            except ValueError:
                pass

"""Stacked serving fast paths: whole-index evaluation in O(1) dispatches.

The general executor evaluates call trees shard by shard — correct for
every call, but each shard costs device dispatches. For the serving-critical
calls — Count, TopN, Sum, Min, Max, GroupBy (executor.go:930,1790,331,1098)
— this module evaluates ALL shards in a constant number of fused XLA
dispatches: fragment rows become [shards, words] stacked planes resident on
device, call trees become jitted elementwise+popcount+reduce programs, and
per-query work is a handful of dispatches and ONE host sync, independent of
the shard count.

Stacks are cached per (kind, index, field, rows, shard-set) and invalidated
by the fragments' write-generation counters, so a stale stack can never serve
a query. A leaf stack (one row) is held to what happened to ITS ROW: per shard
(fragment.uid, fragment.row_generation(row)), behind the view's per-row stamp
(view.stamp(row)) — a write stales only the stacks of the rows it wrote, the
reference's granularity (fragment.rowCache fragment.go:367). Row-chunk and BSI
stacks hold many rows and keep the fragment-wide (uid, fragment.generation),
which every mutation bumps, behind (view.uid, view.mutations). LRU-bounded: at
SHARD_WIDTH=2^20 a 954-shard stack is ~120 MB of HBM, so only the hottest
rows stay resident.

On a multi-device host the stacks are placed sharded over a 1-D "shards"
mesh (zero-padded to a device multiple — zero rows are count-neutral for
every supported op), so the SAME jitted programs are GSPMD partitioned by
XLA: per-device popcounts reduce over ICI instead of one chip doing all the
work (SURVEY §2 parallelism: the shard axis is the one SPMD axis).

Overflow discipline: per-(row,shard) popcounts fit int32 (≤ 2^20), but
totals over shards can exceed 2^31 (a >2048-shard index). TPUs run JAX with
x64 disabled, so instead of int64 accumulators every cross-shard reduce
returns a (hi, lo) int32 pair — hi = Σ(count >> 16), lo = Σ(count & 0xffff)
— combined on host as exact Python ints. Safe to 2^15 shards (32768 shards
≈ 34 trillion columns per node).
"""

import contextlib
import threading
import time
from collections import OrderedDict

import numpy as np

from ..utils import device as _device
from ..utils import flightrec as _flightrec
from ..utils import profile as _profile
from ..utils import tracing as _tracing
from ..utils import workload as _workload
from ..utils.stats import global_stats
from . import adaptive as _adaptive
from . import ingest as _ingest


class _Waiter:
    """One submitted payload in a GroupCommit: what its caller waits on.
    `batch` is set (to the batch it must lead) when the previous leader
    hands leadership to it; `size` is the size of the batch it rode."""

    __slots__ = ("payload", "result", "error", "done", "batch", "size")

    def __init__(self, payload):
        self.payload = payload
        self.result = self.error = self.batch = None
        self.done = threading.Event()
        self.size = 0


class GroupCommit:
    """Group-commit batching for the serving path: ONE leader at a time.

    Per-query device work is already async — XLA queues each fused
    program without blocking — but a launch and the fetch of its result
    cost host time that can dwarf a sub-millisecond device pass, and
    every thread that pays them pays under one interpreter lock. Serving
    threads therefore share them: a caller that finds no batch in flight
    LEADS at once, a batch of one; callers that arrive while a batch is
    in flight queue, and when that batch is done the head of the queue
    leads everything queued with one `process` call (one lock hold, one
    fetch). No timer and no sleep: a lone query never waits, and under
    concurrency a batch is whatever arrived during the previous one —
    the slower a cycle, the larger the next batch.

    Whether a caller leads or queues, and to whom leadership passes, is
    decided under the one `_lock`, so no waiter is ever left without a
    leader. A leader failure (compile error, device OOM, device loss)
    propagates to EVERY waiter of its batch and leadership still passes
    on — events always fire, so no HTTP thread can hang on a dead
    leader."""

    def __init__(self):
        self._lock = threading.Lock()
        self._queue = []          # arrived while a batch is in flight
        self._in_flight = False   # a leader is between taking and passing
        # observability: batched / batches = queries per `process` call
        self.batches = 0
        self.batched = 0

    def submit(self, payload, process):
        """Hand `payload` to the batch leader, who calls
        `process([payloads...]) -> [results...]` once for its whole
        batch. Returns this payload's result; re-raises the leader's
        exception if its batch failed."""
        _tracing.end_current("exec.plan")
        me = _Waiter(payload)
        with self._lock:
            if self._in_flight:
                self._queue.append(me)
                batch = None
            else:
                self._in_flight = True
                batch = [me]
        # dispatch.queue: the wait for the batch in flight — a follower's
        # lasts until its own batch is done, a leader's until it leads
        with _tracing.start_span("dispatch.queue") as span:
            if batch is None:
                me.done.wait()
                # taken off the waiter: a waiter that kept the batch it
                # is part of would be a reference cycle, and its payload
                # (device stacks) would outlive the call until a
                # collection
                batch, me.batch = me.batch, None
            follower = batch is None
            if span is not None:
                span.set_tag("role", "follower" if follower else "leader")
                span.set_tag("batch", me.size if follower else len(batch))
            if follower:
                if me.error is not None:
                    raise me.error
                return me.result
        try:
            results = process([w.payload for w in batch])
            for w, r in zip(batch, results):
                w.result = r
        except BaseException as exc:
            for w in batch:
                w.error = exc
            raise
        finally:
            # leadership passes on once the batch is fetched, not after
            # its launch: on the chip both answered the same rate, and
            # this way makes the fewest launches and fetches
            self._pass_on(len(batch))
            for w in batch:
                if w is not me:
                    w.size = len(batch)
                    w.done.set()
        return me.result

    def _pass_on(self, done):
        """End the batch in flight (of `done` payloads): the head of the
        queue leads everything queued, or nothing is in flight any
        more."""
        with self._lock:
            self.batches += 1
            self.batched += done
            batch, self._queue = self._queue, []
            if not batch:
                self._in_flight = False
                return
        batch[0].batch = batch
        batch[0].done.set()


#: serializes every multi-device launch in this process (see
#: StackedEvaluator.__init__ for the rendezvous-starvation rationale)
_DISPATCH_LOCK = threading.Lock()


class DeadlineExceededError(Exception):
    """The request's deadline lapsed mid-query — raised at the dispatch
    boundary BEFORE the device launch, so expired work never holds the
    dispatch lock. Defined here (not exec/executor.py) so the per-
    dispatch check needs no circular import; server/api.py maps it to
    504."""


_deadline_tls = threading.local()


def set_thread_deadline(at):
    """Arm (or with None, clear) this thread's request deadline — an
    absolute time.monotonic() instant. Checked by _locked_dispatch
    before each lock acquisition; the executor sets it around each
    query's call loop."""
    _deadline_tls.at = at


def _check_thread_deadline():
    at = getattr(_deadline_tls, "at", None)
    if at is not None and time.monotonic() >= at:
        raise DeadlineExceededError(
            "request deadline expired before dispatch")

_SERIAL_EXECUTION = None


def _serial_execution():
    """True when multi-device programs must be held to COMPLETION (not
    just enqueued) one at a time. The CPU backend runs the per-device
    executions of a GSPMD program on a shared thread pool, and the
    in-program cross-shard reduces rendezvous across them — two programs
    in flight can each hold part of the pool at their rendezvous and
    starve each other permanently (observed wedging concurrent serving
    threads on the 8-virtual-device test mesh). Accelerator backends
    execute streams FIFO per device, so enqueue order alone already
    prevents interleaving and overlap stays safe (and async)."""
    global _SERIAL_EXECUTION
    if _SERIAL_EXECUTION is None:
        import jax

        _SERIAL_EXECUTION = jax.default_backend() == "cpu"
    return _SERIAL_EXECUTION


def _launch_barrier(out):
    """Block the locked dispatch until `out` is resident when the
    backend requires serial execution (see _serial_execution)."""
    if _serial_execution():
        import jax

        jax.block_until_ready(out)
    return out


#: dispatch-phase taxonomy (GET /debug/dispatch): lock_wait is measured
#: by _locked_dispatch itself; the others are marked by the dispatch
#: sites between the operations they time. transfer_in exists for sites
#: that explicitly stage host data under the lock — on the current
#: paths dense plane uploads happen on the upload path OUTSIDE the
#: dispatch lock (attributed via planes_uploaded / hbm ledger), so the
#: phase is normally absent. dispatch_ack is relabeled "compile" on a
#: program's first call (detected via the kernel arg-spec cache) because
#: trace+compile dominates that call's fn() wall.
DISPATCH_PHASES = ("lock_wait", "transfer_in", "compile", "dispatch_ack",
                   "sync")


class _PhaseClock:
    """Phase marks within one locked dispatch. `mark(phase)` attributes
    the time since the previous mark (or lock acquisition) to `phase`;
    _locked_dispatch folds any residual into the last mark on exit so
    the per-phase seconds sum EXACTLY to the dispatch wall."""

    __slots__ = ("_t", "compiling", "phases")

    def __init__(self, t1, compiling=False):
        self._t = t1
        self.compiling = compiling
        self.phases = []

    def mark(self, phase):
        now = time.perf_counter()
        if phase == "dispatch_ack" and self.compiling:
            phase = "compile"
        self.phases.append([phase, now - self._t])
        self._t = now


def _device_get_batch(payloads):
    """GroupCommit `process` for plain result fetches: payloads are
    tuples of device values; ONE device_get resolves them all."""
    flat = [a for arrays in payloads for a in arrays]
    vals = fetch(flat)
    out = []
    i = 0
    for arrays in payloads:
        out.append(vals[i:i + len(arrays)])
        i += len(arrays)
    return out



def _named_jit(name, fn):
    """`jax.jit(fn)` under a name of its own, the body inside a
    `jax.named_scope` of the same name: the profiler's trace then says
    `PjitFunction(<name>)` and `jit_<name>` where every program of this
    module used to be `fn`, and the program's operations carry the scope."""
    import jax

    def program(*args):
        with jax.named_scope(name):
            return fn(*args)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program)


def _looked_up(span, outcome, stack, planes_uploaded=0):
    """Tag a `stack.lookup` span with how the stack was come by (`hit`,
    `stale`: served as it is under pending ingest deltas, `patch`,
    `build`) and the planes that went to the device for it."""
    if span is not None:
        span.set_tag("outcome", outcome)
        span.set_tag("planes_uploaded", planes_uploaded)
    return stack


def _placed(span, nbytes, repr_kind):
    """Tag a `stack.place` span (container choice and upload, or a
    patch's scatter) with what went to the device."""
    if span is not None:
        span.set_tag("bytes", int(nbytes))
        span.set_tag("repr", repr_kind)


def fetch(arrays):
    """Every result fetch of the serving path: the wait for the device
    and the device->host copy of `arrays`, as the `dispatch.fetch` stage
    (outside the dispatch lock and outside `stacked.kernel`)."""
    import jax

    with _tracing.start_span("dispatch.fetch", arrays=len(arrays)):
        return jax.device_get(arrays)


from ..core.fragment import BSI_EXISTS_BIT, BSI_OFFSET_BIT, BSI_SIGN_BIT
from ..core.index import EXISTENCE_FIELD_NAME
from ..core.view import VIEW_STANDARD
from ..shardwidth import WORDS_PER_ROW

# Device-byte budgets for cached stacks; excess evicts least-recently-used.
# (Entry size scales with shard count — ~120 MB per 954-shard stack — so a
# count bound alone could pin several GB of HBM.) Two pools: leaf/BSI
# stacks, and TopN/GroupBy row-chunk stacks ([rows, shards, words] keyed by
# the exact candidate tuple), which are large and churn with any
# candidate-set change, so they must not be able to evict the long-lived
# leaf/BSI stacks the Count/Sum serving paths depend on.
#
# The budgets in force are `budgets()`: shares of the memory the device
# reports (`utils/device.memory_bytes`) — a quarter of one device for the
# leaf/BSI pool, an eighth for the rows pool, times the local devices a
# stack is sharded over (each holds 1/n of every stack). The other five
# eighths are for what is no cached stack: the stacks that writes replace
# while batches in flight still hold the old ones (4.3–5.9 GB at 32
# clients with 5 % imports, PERF.md section 5), a build's upload in
# flight, the programs' temporaries. Over-budget costs a rebuild; out of
# memory costs the query. The two constants stand where the backend
# reports no memory (the host CPU).
MAX_STACK_BYTES = 512 * 1024 * 1024
MAX_ROWS_STACK_BYTES = 256 * 1024 * 1024
STACK_MEMORY_DIVISOR = 4            # of one device's bytes_limit
ROWS_STACK_MEMORY_DIVISOR = 8


def budgets():
    """(leaf/BSI pool, rows pool) byte budgets in force: every reader of a
    budget calls this. Observed, never configured; initialises no
    backend (before one exists the constants are in force, and nothing is
    cached yet to hold to them)."""
    per_device = _device.memory_bytes()
    if per_device is None:
        return MAX_STACK_BYTES, MAX_ROWS_STACK_BYTES
    n = _device.facts()["localDeviceCount"]
    return (per_device // STACK_MEMORY_DIVISOR * n,
            per_device // ROWS_STACK_MEMORY_DIVISOR * n)


# Compiled tree programs are tiny but unbounded shapes would accumulate.
MAX_FNS = 128
# Below this many shards the per-shard path's dispatch count is too small
# to matter.
MIN_SHARDS = 2
# Transient row-chunk stacks ([rows, shards, words]) are built at most this
# large, so TopN/GroupBy dispatch count is O(rows/chunk) — independent of
# the shard count.
CHUNK_BYTES = 128 * 1024 * 1024
# Time-range leaves union one cached stack per quantum view in the range
# cover; wider covers (a years-long hourly span) use the per-shard path.
MAX_TIME_VIEWS = 64

_OPS = {"Intersect": "&", "Union": "|", "Difference": "-", "Xor": "^"}

#: a count signature's root operator as data: the code a slot of a bucket
#: program carries in its `ops` vector, in the order of the program's
#: branches. A spare slot carries PAD_OP, the branch that reads nothing.
ROOT_OPS = ("&", "|", "^", "-")
PAD_OP = len(ROOT_OPS)


def _split_root(sig):
    """(shape, operator code) of a count signature: the shape is the
    signature with its root operator taken out — (None, subs), or a bare
    leaf as it is, under code 0. Operators below the root stay in the
    shape."""
    if sig[0] == "leaf":
        return sig, 0
    return (None, sig[1]), ROOT_OPS.index(sig[0])


def _bucket_args(chunk, size):
    """A `size`-slot bucket program's arguments for `chunk`, (position,
    signature, operator code, flat leaves) of each query it answers: the
    `ops` vector, the queries' leaves, and for each spare slot PAD_OP and
    the first query's leaves (references: the slot reads none)."""
    ops = np.full(size, PAD_OP, dtype=np.int32)
    ops[:len(chunk)] = [op for _, _, op, _ in chunk]
    args = [ops]
    for _, _, _, flat in chunk:
        args.extend(flat)
    args.extend(chunk[0][3] * (size - len(chunk)))
    return args


def _launch_plan(n, cap):
    """(slots, queries) of each launch that sends a group of `n`: whole
    launches of `cap`, then the rest in ONE launch of the next power of
    two (37 under 32 is 32 + 8 with three spare slots); one slot is the
    solo program."""
    plan = [(cap, cap)] * (n // cap)
    rest = n % cap
    if rest:
        plan.append((1 << (rest - 1).bit_length(), rest))
    return plan


#: thread-local batch attribution: the batch paths stamp how many
#: queries shared the thread's last fused dispatch, the executor reads
#: it back for strategy notes / SLOW QUERY `batch=` attribution.
_BATCH_TLS = threading.local()


def note_batch_size(n):
    """Record the fused-batch size the current thread's query rode
    (0 resets; 1 = solo dispatch)."""
    _BATCH_TLS.size = int(n)


def last_batch_size():
    """Fused-batch size stamped by the last batched dispatch on THIS
    thread (0 when the thread never rode one)."""
    return getattr(_BATCH_TLS, "size", 0)


_UNSET = object()

from ..ops import bitplane  # noqa: E402
from ..ops import containers as _containers  # noqa: E402
from ..ops.bitplane import combine_hi_lo  # noqa: E402  (canonical helper)


def time_range_views(idx, field_name, args):
    """Quantum-view name cover for a time-range Row, or None when the
    field isn't a time field / has no quantum. Pure function of the
    REPLICATED schema + call args (both stacked and SPMD leaves use it;
    semantics identical to the executor's per-shard _row_shard)."""
    from ..core import timeq
    from ..core.field import FIELD_TYPE_TIME

    field = idx.field(field_name)
    if field is None or field.type != FIELD_TYPE_TIME:
        return None
    quantum = field.time_quantum()
    if not quantum:
        return None
    try:
        from_t = timeq.parse_time(args["from"]) if "from" in args \
            else timeq.parse_time("1970-01-01T00:00")
        to_t = timeq.parse_time(args["to"]) if "to" in args \
            else timeq.parse_time("2100-01-01T00:00")
    except Exception:
        return None  # malformed timestamps: per-shard path raises cleanly
    views = tuple(timeq.views_by_time_range(
        VIEW_STANDARD, from_t, to_t, quantum))
    if len(views) > MAX_TIME_VIEWS:
        return None  # a huge hourly span: per-shard path handles it
    return views


def intern_time_leaf(idx, field_name, row_id, args, leaves):
    '''THE ("timerow", field, row, views) leaf interner, shared by the
    stacked and SPMD signature walks so the leaf key shape lives in one
    place (both sides consult only replicated schema).'''
    views = time_range_views(idx, field_name, args)
    if views is None:
        return None
    key = ("timerow", field_name, int(row_id), views)
    if key not in leaves:
        leaves[key] = len(leaves)
    return ("leaf", leaves[key])


def tree_signature(idx, call, leaves, leaf, bsi_leaf=None, time_leaf=None):
    """THE coverage walk for stacked/SPMD fast paths: turns a bitmap call
    tree into an operator signature over leaf slots, or None when any
    shape isn't expressible (Shift, keys, ...).
    `leaf(idx, field_name, row_id, leaves)` decides row-leaf eligibility —
    the stacked evaluator requires a local standard view; the SPMD plane
    checks replicated schema only (cluster/spmd.py).
    `bsi_leaf(idx, field_name, cond, leaves)` (optional) covers BSI
    condition leaves like Row(v > 10) the same way (reference algorithm:
    fragment.go:1357-1470); None declines conditions entirely.
    `time_leaf(idx, field_name, row_id, args, leaves)` (optional) covers
    time-range rows Row(t=1, from=..., to=...) as a union over the
    quantum-view cover (reference: viewsByTimeRange time.go:91); None
    declines time ranges entirely."""
    name = call.name
    if name in ("Row", "Range"):
        if "from" in call.args or "to" in call.args:
            if time_leaf is None or call.has_conditions():
                return None
            field_name = call.field_arg()
            if field_name is None:
                return None
            row_id = call.args.get(field_name)
            if isinstance(row_id, bool):
                row_id = int(row_id)
            if not isinstance(row_id, int):
                return None
            return time_leaf(idx, field_name, row_id, call.args, leaves)
        if call.has_conditions():
            if bsi_leaf is None or len(call.args) != 1:
                return None
            from ..pql import Condition

            field_name, cond = next(iter(call.args.items()))
            if not isinstance(cond, Condition):
                return None
            return bsi_leaf(idx, field_name, cond, leaves)
        field_name = call.field_arg()
        if field_name is None:
            return None
        row_id = call.args.get(field_name)
        if isinstance(row_id, bool):
            row_id = int(row_id)
        if not isinstance(row_id, int):
            return None
        return leaf(idx, field_name, row_id, leaves)
    if name in _OPS and call.children:
        subs = tuple(
            tree_signature(idx, c, leaves, leaf, bsi_leaf, time_leaf)
            for c in call.children)
        if any(s is None for s in subs):
            return None
        return (_OPS[name], subs)
    if name == "Not" and len(call.children) == 1 \
            and idx.options.track_existence \
            and idx.field(EXISTENCE_FIELD_NAME) is not None:
        child = tree_signature(idx, call.children[0], leaves, leaf,
                               bsi_leaf, time_leaf)
        if child is None:
            return None
        exists = leaf(idx, EXISTENCE_FIELD_NAME, 0, leaves)
        if exists is None:
            return None
        return ("-", (exists, child))
    return None


def tree_eval(sig, stacks):
    """THE traced operator-tree evaluator over aligned leaf stacks —
    module-level entry so the SPMD collective programs (cluster/spmd.py)
    share the exact expression semantics of the local serving kernels
    instead of reaching into StackedEvaluator internals."""
    return StackedEvaluator._tree_eval(sig, stacks)


class StackedEvaluator:
    def __init__(self):
        self._stacks = OrderedDict()  # key -> (gens, device arrays, nbytes)
        self._stack_bytes = 0
        self._rows_stacks = OrderedDict()  # row-chunk pool (own budget)
        self._rows_stack_bytes = 0
        self._fns = OrderedDict()     # kernel signature -> jitted fn
        # Cross-query batching (GroupCommit): result-fetch amortization
        # for Sum, and full dispatch batching for Count (queued queries
        # fuse into ONE program per signature bucket + ONE fetch).
        self._fetch_commit = GroupCommit()
        self._count_commit = GroupCommit()
        # device launches made for count batches, the spare slots they
        # carried, and chunks that went out in other programs because
        # their bucket was not built yet (its build thread is in
        # _count_builds until the program is in _fns)
        self.count_launches = 0
        self.count_pad_slots = 0
        self.count_batch_fallbacks = 0
        self._count_builds = {}
        self._lock = threading.Lock()
        # Multi-device dispatches must not interleave: stacks are
        # mesh-sharded, so every serving program is a GSPMD launch across
        # all local devices whose cross-shard reduces rendezvous between
        # the per-device executions. Concurrent serving threads wedge
        # that rendezvous on backends without per-device FIFO streams,
        # so each launch holds this lock — for the enqueue everywhere,
        # and through completion where _serial_execution() says overlap
        # is unsafe (the CPU thread-pool backend). Result fetches stay
        # outside it. The lock is PROCESS-wide, not per-evaluator: the
        # devices are a process-level resource, and the in-process
        # cluster harness runs several evaluators over the same mesh.
        self._dispatch_lock = _DISPATCH_LOCK
        self._sharding = _UNSET
        # Kernel-dispatch counter: tests assert serving dispatch counts are
        # independent of the shard count.
        self.dispatches = 0
        # Cache observability (exported at /debug/vars "stacked"): without
        # these, budget thrash (VERDICT r2) is invisible in production.
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # Per-pool eviction counters tagged by cause ("budget" LRU
        # pressure vs "invalidate" full flushes), mirrored to /metrics as
        # stacked_evictions_total{pool,cause}; the untagged total above
        # stays for back-compat with older dashboards.
        self.pool_evictions = {}
        # HBM ledger: resident stack-cache bytes attributed per
        # (index, field, pool), maintained exactly in lockstep with
        # _stack_bytes/_rows_stack_bytes by _cache_put/invalidate and
        # exported as hbm_stack_bytes{index,field,pool} gauges +
        # GET /debug/hbm. Answers "what is resident in HBM and for whom".
        self._hbm_ledger = {}
        # Per-kernel attribution: kind -> {count, seconds, bytes_in,
        # bytes_out} fed by _locked_dispatch; arg shape specs captured on
        # each compiled fn's first call so /debug/kernels can compute
        # jax cost_analysis() lazily (never on the serving path).
        self._kernels = {}
        self._fn_specs = {}
        self._kernel_costs = {}
        # Dispatch-phase decomposition: kind -> {phase: {count, seconds}}
        # fed by _locked_dispatch's phase clock (GET /debug/dispatch) —
        # splits the per-dispatch RTT into lock_wait / transfer_in /
        # compile / dispatch_ack / sync so a slow round trip is
        # attributable.
        self._dispatch_phases = {}
        # Incremental-maintenance observability: a patch re-uploads only
        # the drifted shards' planes instead of the whole stack; tests
        # assert planes_uploaded stays O(changed shards) under writes.
        self.patches = 0
        self.planes_uploaded = 0
        # Cold builds (a lookup's outcome `build`: every plane gathered
        # from the fragments and uploaded) and where their seconds go:
        # the whole build, and of it the host gather (`_host_rows`); the
        # rest is container choice and upload. A patch counts above.
        self.builds = 0
        self.build_seconds = 0.0
        self.build_gather_seconds = 0.0
        # Streaming-ingest observability: reads served from a stale
        # stack whose drift is fully covered by pending ingest deltas
        # (the merge folds them off the read path; exec/ingest.py).
        self.stale_serves = 0
        # Pairwise GroupBy observability: dispatches and host syncs must
        # stay O(⌈R1/tile⌉·⌈R2/tile⌉) for a two-field cross product —
        # tests assert these, not wall time (which is noisy on CPU).
        self.pairwise_dispatches = 0
        self.pairwise_syncs = 0
        # Whole-plan fusion observability (GET /debug/fusion): queries
        # whose every top-level Count rode ONE fused device program.
        self.fused_dispatches = 0

    def _stack_sharding(self):
        """NamedSharding over all local devices (None on a single device),
        resolved lazily so importing this module never touches the
        backend."""
        if self._sharding is _UNSET:
            import jax

            # local_devices: host-local numpy stacks can't be placed onto
            # other processes' chips; cross-host scale-out is the cluster
            # layer's job (shards_by_node), not this cache's.
            devices = jax.local_devices()
            if len(devices) < 2:
                self._sharding = None
            else:
                mesh = jax.sharding.Mesh(np.array(devices), ("shards",))
                self._sharding = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec("shards"))
        return self._sharding

    def _n_pad_devices(self):
        sharding = self._stack_sharding()
        return 1 if sharding is None else len(sharding.device_set)

    def _padded_len(self, shards):
        """Shard-axis length zero-padded to a device multiple. Load-bearing
        agreement: filter [S_pad, W] and rows [R, S_pad, W] stacks must use
        the SAME padding or their elementwise combine misaligns."""
        n_dev = self._n_pad_devices()
        return ((len(shards) + n_dev - 1) // n_dev) * n_dev

    def _place(self, host_stack, shard_axis):
        """Upload a host stack, sharded over the device mesh along
        `shard_axis` (already zero-padded by the caller)."""
        import jax

        sharding = self._stack_sharding()
        if sharding is None:
            return jax.device_put(host_stack)
        spec = [None] * host_stack.ndim
        spec[shard_axis] = "shards"
        return jax.device_put(host_stack, jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec(*spec)))

    def _place_replicated(self, host_array):
        """Upload a compressed container component replicated across the
        mesh: compressed arrays have no shard axis to partition, and an
        explicitly replicated operand keeps the serving program a valid
        GSPMD launch next to mesh-sharded dense stacks (XLA reshards as
        needed). On a single device this is a plain device_put."""
        import jax

        sharding = self._stack_sharding()
        if sharding is None:
            return jax.device_put(host_array)
        return jax.device_put(host_array, jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec()))

    # -- tree analysis -------------------------------------------------------

    def _leaf(self, idx, field_name, row_id, leaves):
        field = idx.field(field_name)
        if field is None or field.view(VIEW_STANDARD) is None:
            return None
        # tagged key: a field literally named "bsicond" must not collide
        # with condition-leaf keys in the shared leaves dict
        key = ("row", field_name, int(row_id))
        if key not in leaves:
            leaves[key] = len(leaves)
        return ("leaf", leaves[key])

    def _bsi_leaf(self, idx, field_name, cond, leaves):
        """Condition-leaf eligibility: an int field with a local BSI view
        and a normalizable condition. The leaf key carries (op, values) so
        identical conditions share one slot."""
        from .bsicond import normalize_bsi_condition

        field = idx.field(field_name)
        if field is None or field.options.type != "int" \
                or field.view(field.bsi_view_name()) is None:
            return None
        norm = normalize_bsi_condition(cond)
        if norm is None:
            return None
        op, vals = norm
        key = ("bsicond", field_name, op, vals)
        if key not in leaves:
            leaves[key] = len(leaves)
        return ("leaf", leaves[key])

    def signature(self, idx, call, leaves):
        """Tree signature with leaf slots, or None when the tree has any
        shape the fast path doesn't cover (Shift, keys...). None means:
        use the general per-shard path."""
        return tree_signature(idx, call, leaves, self._leaf, self._bsi_leaf,
                              intern_time_leaf)

    # -- stack cache ---------------------------------------------------------

    def _fragment_gens(self, idx, field_name, shards,
                       view_name=VIEW_STANDARD, view=None, row_id=None):
        """Cache-validation fingerprint: per-shard (fragment uid,
        generation) — the generation of `row_id` alone where the stack
        holds that one row (a leaf), so drift shows only where the row's
        plane may have moved; the fragment-wide one otherwise. The uid
        makes a recreated fragment (field dropped and re-made at the same
        path) distinct from its predecessor even when the generation
        counters collide. None when the field vanished (concurrent DDL) —
        caller falls back to the general path. Callers that already
        resolved the view pass it to skip the double field/view lookup on
        the serving path."""
        if view is None:
            field = idx.field(field_name)
            view = field.view(view_name) if field is not None else None
            if view is None:
                return None
        frags = [view.fragment(shard) for shard in shards]
        if row_id is None:
            return tuple((-1, -1) if frag is None
                         else (frag.uid, frag.generation) for frag in frags)
        return tuple((-1, -1) if frag is None
                     else (frag.uid, frag.row_generation(row_id))
                     for frag in frags)

    def _pool(self, key):
        """(pool, its budget): row-chunk stacks live in their own LRU
        pool (see `budgets`)."""
        stack_budget, rows_budget = budgets()
        if key[0] == "rows":
            return self._rows_stacks, rows_budget
        return self._stacks, stack_budget

    @staticmethod
    def _heat_key(key):
        """(index, field, view) for the fragment heat ledger. Leaf and
        rows stacks cache the standard view (rows keys carry the actual
        view name at key[3] — time-quantum views differ); BSI stacks
        cache the field's BSI bit planes."""
        if key[0] == "rows":
            return key[1], key[2], key[3]
        if key[0] == "bsi":
            return key[1], key[2], "bsi"
        return key[1], key[2], VIEW_STANDARD

    def _cache_get_fast(self, key, stamp):
        """O(1) hit check via the view-level stamp (View.stamp) — the
        first level of the two-level fingerprint. A stamp match proves
        nothing the stack holds changed since the entry was stored (for a
        leaf: its row, in any fragment of the view), so the
        per-shard generation walk (954 iterations at 1B columns — the
        dominant per-query Python cost) is skipped entirely on the hot
        serving path."""
        pool, _ = self._pool(key)
        with self._lock:
            hit = pool.get(key)
            if hit is not None and hit[3] == stamp:
                pool.move_to_end(key)
                hit[4] = time.time()  # last-hit age for /debug/hbm
                self.hits += 1
                hit = hit[1]
            else:
                hit = None
        if hit is not None:
            # heat rides every probe that RESOLVED here (outside the
            # evaluator lock: the ledger has its own)
            _workload.heat_bump(*self._heat_key(key))
        return hit

    def _cache_get(self, key, gens, stamp=None):
        """Second-level check: exact per-shard generations. On a hit the
        entry's stamp refreshes — a mutation elsewhere in the view (e.g.
        a new fragment outside this stack's shard set) bumps the counter
        without changing these gens, and without the refresh every later
        query would pay the slow walk again."""
        pool, _ = self._pool(key)
        with self._lock:
            hit = pool.get(key)
            if hit is not None and hit[0] == gens:
                pool.move_to_end(key)
                if stamp is not None:
                    hit[3] = stamp
                hit[4] = time.time()
                self.hits += 1
                hit = hit[1]
            else:
                self.misses += 1
                hit = None
        # misses bump too: demand for an absent fragment is precisely
        # what makes it an admission candidate in /debug/heat
        _workload.heat_bump(*self._heat_key(key))
        return hit

    def _ledger_key(self, key, repr_kind):
        """Every cache key carries (kind, index, field, ...) at positions
        0-2; the ledger attributes bytes per (index, field, pool, repr) —
        the repr dimension is what makes /debug/hbm answer "how much of
        the residency is compressed" (rows/BSI pools are always dense)."""
        pool_name = "rows" if key[0] == "rows" else "stack"
        return (key[1], key[2], pool_name, repr_kind)

    def _ledger_add(self, key, delta, repr_kind="dense"):
        """Move the HBM ledger in lockstep with the pool byte counters
        (caller holds self._lock). Gauges update here too: puts/evicts
        are cache-fill events, not per-query hot path."""
        lkey = self._ledger_key(key, repr_kind)
        new = self._hbm_ledger.get(lkey, 0) + delta
        if new <= 0:
            self._hbm_ledger.pop(lkey, None)
            new = 0
        else:
            self._hbm_ledger[lkey] = new
        index, field, pool_name, repr_kind = lkey
        global_stats.gauge("hbm_stack_bytes", new, {
            "index": index, "field": field, "pool": pool_name,
            "repr": repr_kind})

    def _count_eviction(self, pool_name, cause, n=1):
        """Per-pool, cause-tagged eviction counters (caller holds
        self._lock); exported as stacked_evictions_total{pool,cause}."""
        k = (pool_name, cause)
        self.pool_evictions[k] = self.pool_evictions.get(k, 0) + n
        global_stats.count("stacked_evictions", n,
                           {"pool": pool_name, "cause": cause})

    def _pop_victim(self, pool):
        """One over-budget victim (caller holds self._lock). Legacy LRU
        (FIFO position) when the adaptive engine is off; lowest
        heat×cost benefit score when on — which may be the entry just
        inserted, making the score an admission filter too; shadow
        scores, counts the divergence, and still evicts LRU. Heat reads
        are decayed point lookups in the workload ledger (its own lock —
        the ledger never calls back into this module, so the ordering is
        one-way)."""
        amode = _adaptive.cache_mode()
        lru_key = next(iter(pool))
        if amode == "off":
            ekey = lru_key
        else:
            heat = _workload.heat()
            best = _adaptive.select_victim(
                [(k, heat.value(*self._heat_key(k)), e[2])
                 for k, e in pool.items()])
            if amode == "on":
                ekey = best
                _adaptive.note_eviction("benefit")
            else:
                ekey = lru_key
                _adaptive.note_eviction("lru", diverged=best != lru_key)
        return ekey, pool.pop(ekey)

    def _cache_put(self, key, gens, arrays, nbytes, stamp=None):
        pool, budget = self._pool(key)
        rows = pool is self._rows_stacks
        pool_name = "rows" if rows else "stack"
        repr_kind = _containers.kind_of(arrays)
        evicted_keys = []
        with self._lock:
            old = pool.pop(key, None)
            if old is not None:
                if rows:
                    self._rows_stack_bytes -= old[2]
                else:
                    self._stack_bytes -= old[2]
                self._ledger_add(key, -old[2],
                                 _containers.kind_of(old[1]))
            pool[key] = [gens, arrays, nbytes, stamp, time.time()]
            self._ledger_add(key, nbytes, repr_kind)
            if rows:
                self._rows_stack_bytes += nbytes
                while self._rows_stack_bytes > budget and len(pool) > 1:
                    ekey, evicted = self._pop_victim(pool)
                    self._rows_stack_bytes -= evicted[2]
                    self.evictions += 1
                    self._ledger_add(ekey, -evicted[2],
                                     _containers.kind_of(evicted[1]))
                    self._count_eviction(pool_name, "budget")
                    evicted_keys.append((ekey, evicted[2]))
            else:
                self._stack_bytes += nbytes
                while self._stack_bytes > budget and len(pool) > 1:
                    ekey, evicted = self._pop_victim(pool)
                    self._stack_bytes -= evicted[2]
                    self.evictions += 1
                    self._ledger_add(ekey, -evicted[2],
                                     _containers.kind_of(evicted[1]))
                    self._count_eviction(pool_name, "budget")
                    evicted_keys.append((ekey, evicted[2]))
        _flightrec.record("cache.put", pool=pool_name, index=key[1],
                          field=key[2], bytes=nbytes, repr=repr_kind)
        for ekey, ebytes in evicted_keys:
            _flightrec.record("cache.evict", pool=pool_name, index=ekey[1],
                              field=ekey[2], bytes=ebytes, cause="budget")

    def merge_swap(self, key, old_entry, gens, arrays, nbytes):
        """Install an ingest merge's result over the exact entry it was
        planned from (identity compare — a concurrent rebuild or
        eviction wins and the merge result is dropped). The entry
        updates IN PLACE under the lock; the stamp resets to None so the
        next read revalidates with one gens walk instead of trusting a
        view-stamp that predates the merge. Returns True on install."""
        pool, _ = self._pool(key)
        rows_pool = pool is self._rows_stacks
        with self._lock:
            cur = pool.get(key)
            if cur is not old_entry:
                return False
            old_bytes = cur[2]
            old_kind = _containers.kind_of(cur[1])
            cur[0] = gens
            cur[1] = arrays
            cur[2] = nbytes
            cur[3] = None
            cur[4] = time.time()
            if rows_pool:
                self._rows_stack_bytes += nbytes - old_bytes
            else:
                self._stack_bytes += nbytes - old_bytes
            self._ledger_add(key, -old_bytes, old_kind)
            self._ledger_add(key, nbytes, _containers.kind_of(arrays))
        self._note_patch("merge")
        return True

    def merge_drop(self, key, old_entry):
        """Evict an entry the ingest merge decided not to fold (too
        drifted, vanished field): the next read rebuilds cold. Identity
        compare like merge_swap. Returns True when dropped."""
        pool, _ = self._pool(key)
        rows_pool = pool is self._rows_stacks
        with self._lock:
            cur = pool.get(key)
            if cur is not old_entry:
                return False
            pool.pop(key)
            if rows_pool:
                self._rows_stack_bytes -= cur[2]
            else:
                self._stack_bytes -= cur[2]
            self.evictions += 1
            self._ledger_add(key, -cur[2], _containers.kind_of(cur[1]))
            self._count_eviction("rows" if rows_pool else "stack",
                                 "ingest")
        return True

    def leaf_stack(self, idx, field_name, row_id, shards):
        """Cached Container of one row's [S, W] plane stack over
        `shards` — the per-fragment representation chooser's call site:
        a cold build analyzes the host stack's measured density and
        picks dense / block-sparse / run-length per the configured
        --container-repr mode (ops/containers.choose)."""
        _tracing.end_current("exec.plan")
        with _tracing.start_span("stack.lookup", pool="leaf",
                                 field=field_name) as span:
            return self._leaf_stack(idx, field_name, row_id, shards, span)

    def _leaf_stack(self, idx, field_name, row_id, shards, span):
        key = ("leaf", idx.name, field_name, row_id, shards)
        field = idx.field(field_name)
        view = field.view(VIEW_STANDARD) if field is not None else None
        if view is None:
            return None
        stamp = view.stamp(row_id)
        hit = self._cache_get_fast(key, stamp)
        if hit is not None:
            return _looked_up(span, "hit", hit)
        gens = self._fragment_gens(idx, field_name, shards, view=view,
                                   row_id=row_id)
        if gens is None:
            return None
        hit = self._cache_get(key, gens, stamp)
        if hit is not None:
            return _looked_up(span, "hit", hit)
        # Incremental maintenance: when k << S shards drifted (a write
        # bumps only its fragment's generation of the rows it wrote),
        # gather + upload ONLY those planes and scatter them into the
        # cached device stack — the device analog of the reference's
        # op-log-over-snapshot delta (roaring.go:228-249) — instead of
        # re-uploading the whole [S, W] stack for a single set_bit. A
        # compressed container has no per-shard planes to scatter into,
        # so it decompresses ON DEVICE once and the fragment decays to
        # dense under write churn — the same convert-on-mutation policy
        # as the reference's roaring containers; the chooser
        # re-compresses at the next full rebuild/readmission, when the
        # density is known again.
        stale = self._stale_entry(key, gens)
        if stale is not None:
            if self._serve_stale(key, idx.name, field_name, VIEW_STANDARD,
                                 shards, stale, gens):
                return _looked_up(span, "stale", stale[1])
            changed = self._changed_shards(stale[0], gens, shards)
            if changed is not None:
                import jax.numpy as jnp

                block = self._host_rows(
                    view, [row_id], [shards[j] for j in changed],
                    pad=False)
                ent = stale[1]
                with _tracing.start_span("stack.place") as place:
                    if isinstance(ent, _containers.Container) \
                            and ent.kind != "dense":
                        old = _containers.container_to_dense(ent)
                    elif isinstance(ent, _containers.Container):
                        old = ent.arrays[0]
                    else:
                        old = ent
                    stack = self._place(
                        old.at[np.asarray(changed)].set(
                            jnp.asarray(block[0])), shard_axis=0)
                    cont = _containers.dense_container(stack)
                    _placed(place, cont.nbytes, "dense")
                self._note_patch("read")
                self._cache_put(key, gens, cont, cont.nbytes, stamp)
                return _looked_up(span, "patch", cont, len(changed))
        cont = self._cold_build(
            view, [row_id], shards, lambda host: _containers.build(
                host[0],
                place_sharded=lambda a: self._place(a, shard_axis=0),
                place_replicated=self._place_replicated,
                fragment=(idx.name, field_name, VIEW_STANDARD, row_id)))
        self._cache_put(key, gens, cont, cont.nbytes, stamp)
        return _looked_up(span, "build", cont, len(shards))

    def _host_rows(self, view, row_ids, shards, pad=True):
        """Host [R, S_padded, W] uint32 gather of rows over shards
        (pad=False skips the device-multiple padding — patch gathers
        address existing stack rows directly): the `stack.gather` stage.

        The per-shard gathers fan out over the shared worker pool: each
        task fills its own out[:, j] column (disjoint slices, so the
        writes need no lock) and the numpy copies release the GIL. This
        is the cold-build hot path — 954 shards × rows of one-at-a-time
        copies before."""
        from ..utils.workpool import get_pool

        planes = len(row_ids) * len(shards)
        with _tracing.start_span("stack.gather", planes=planes):
            n = self._padded_len(shards) if pad else len(shards)
            out = np.zeros((len(row_ids), n, WORDS_PER_ROW),
                           dtype=np.uint32)

            def gather_column(j):
                frag = view.fragment(shards[j])
                if frag is None:
                    return
                for i, row_id in enumerate(row_ids):
                    plane = frag.row_plane(row_id)
                    if plane is not None:
                        out[i, j] = np.asarray(plane)

            get_pool().map_ordered(gather_column, range(len(shards)))
        self.planes_uploaded += planes
        return out

    def _cold_build(self, view, row_ids, shards, place):
        """A lookup's outcome `build`: every plane gathered from the
        fragments (`_host_rows`), then `place(host)` — container choice
        and upload, the `stack.place` stage — whose device value is
        returned. Feeds `builds`, `build_seconds`, `build_gather_seconds`;
        always on: three clock reads a build, which takes a tenth of a
        second and more."""
        t0 = time.perf_counter()
        host = self._host_rows(view, row_ids, shards)
        gathered = time.perf_counter() - t0
        with _tracing.start_span("stack.place") as span:
            placed = place(host)
            _placed(span, placed.nbytes, _containers.kind_of(placed))
        seconds = time.perf_counter() - t0
        with self._lock:
            self.builds += 1
            self.build_seconds += seconds
            self.build_gather_seconds += gathered
        return placed

    def _stale_entry(self, key, gens):
        """(old_gens, arrays, nbytes) of a cached entry whose generations
        drifted, or None. Read under the lock; the returned arrays are
        immutable device buffers so using them outside the lock is safe."""
        pool, _ = self._pool(key)
        with self._lock:
            entry = pool.get(key)
            if entry is None or len(entry[0]) != len(gens):
                return None
            return entry

    def _note_patch(self, path):
        """Count one incremental stack patch, tagged by where it ran:
        "read" = legacy in-query repair, "merge" = the ingest engine's
        interval fold. Exported as stacked_patches_total{path} so the two
        are distinguishable on /metrics (the ingest tests assert the
        read-path count stays flat while deltas are pending)."""
        self.patches += 1
        global_stats.count("stacked_patches", 1, {"path": path})

    def _serve_stale(self, key, index_name, field_name, view_name, shards,
                     stale, gens):
        """True when a stale entry may serve AS-IS because every drifted
        shard is covered by a pending ingest delta (exec/ingest.py) — the
        interval merge folds the drift off the read path; staleness is
        bounded by the merge interval. One list check when no ingest
        engine is active (the default)."""
        if not _ingest.covers_pending(index_name, field_name, view_name,
                                      shards, stale[0], gens):
            return False
        self.stale_serves += 1
        global_stats.count("stacked_stale_serves", 1)
        return True

    def _changed_shards(self, old_gens, gens, shards, rows=1):
        """Stack row indices whose (uid, generation) drifted, or None
        when a device patch isn't worthwhile. The cutoff is the static
        half-the-shards rule (a scatter past it costs about as much as a
        rebuild) — except under --adaptive on, where the cost model
        prices upload vs on-device copy bytes (exec/adaptive.decide_patch)
        and typically patches up to ~7/8 drift."""
        changed = [j for j, (o, n) in enumerate(zip(old_gens, gens))
                   if o != n]
        if not changed:
            return None
        if _adaptive.acting():
            if not _adaptive.decide_patch(len(changed), len(shards), rows,
                                          WORDS_PER_ROW * 4):
                return None
        elif len(changed) * 2 > len(shards):
            return None
        return changed

    def rows_stack(self, idx, field_name, row_chunk, shards,
                   view_name=VIEW_STANDARD, cache=True):
        """Cached [R, S, W] device stack of a chunk of rows (TopN/GroupBy
        candidates). `row_chunk` must be a tuple (cache key). cache=False
        builds a transient stack (freed after use) — callers pass it when
        the full candidate set exceeds the rows pool, so oversized scans
        don't churn out every reusable chunk."""
        _tracing.end_current("exec.plan")
        with _tracing.start_span("stack.lookup", pool="rows",
                                 field=field_name) as span:
            return self._rows_stack(idx, field_name, row_chunk, shards,
                                    view_name, cache, span)

    def _rows_stack(self, idx, field_name, row_chunk, shards, view_name,
                    cache, span):
        key = ("rows", idx.name, field_name, view_name, row_chunk, shards)
        field = idx.field(field_name)
        view = field.view(view_name) if field is not None else None
        if view is None:
            return None
        stamp = view.stamp()
        if cache:
            hit = self._cache_get_fast(key, stamp)
            if hit is not None:
                return _looked_up(span, "hit", hit)
        gens = self._fragment_gens(idx, field_name, shards, view_name,
                                   view=view)
        if gens is None:
            return None
        hit = self._cache_get(key, gens, stamp if cache else None)
        if hit is not None:
            return _looked_up(span, "hit", hit)
        if cache:
            stale = self._stale_entry(key, gens)
            if stale is not None:
                if self._serve_stale(key, idx.name, field_name, view_name,
                                     shards, stale, gens):
                    return _looked_up(span, "stale", stale[1])
                changed = self._changed_shards(stale[0], gens, shards,
                                               rows=len(row_chunk))
                if changed is not None:
                    import jax.numpy as jnp

                    block = self._host_rows(
                        view, list(row_chunk),
                        [shards[j] for j in changed], pad=False)
                    with _tracing.start_span("stack.place") as place:
                        stack = self._place(
                            stale[1].at[:, np.asarray(changed)].set(
                                jnp.asarray(block)), shard_axis=1)
                        _placed(place, stack.size * 4, "dense")
                    self._note_patch("read")
                    self._cache_put(key, gens, stack, stack.size * 4,
                                    stamp)
                    return _looked_up(span, "patch", stack,
                                      len(row_chunk) * len(changed))
        stack = self._cold_build(
            view, list(row_chunk), shards,
            lambda host: self._place(host, shard_axis=1))
        if cache:
            self._cache_put(key, gens, stack, stack.size * 4, stamp)
        return _looked_up(span, "build", stack,
                          len(row_chunk) * len(shards))

    def bsi_stack(self, idx, field_name, shards):
        """Cached (planes [D,S,W], sign [S,W], exists [S,W]) device stacks
        of a BSI field's bit-plane rows (reference layout fragment.go:91-93).
        None when the field/view vanished."""
        _tracing.end_current("exec.plan")
        with _tracing.start_span("stack.lookup", pool="bsi",
                                 field=field_name) as span:
            return self._bsi_stack(idx, field_name, shards, span)

    def _bsi_stack(self, idx, field_name, shards, span):
        field = idx.field(field_name)
        if field is None:
            return None
        view_name = field.bsi_view_name()
        depth = field.options.bit_depth
        key = ("bsi", idx.name, field_name, depth, shards)
        view = field.view(view_name)
        if view is None:
            return None
        stamp = view.stamp()
        hit = self._cache_get_fast(key, stamp)
        if hit is not None:
            return _looked_up(span, "hit", hit)
        gens = self._fragment_gens(idx, field_name, shards, view_name,
                                   view=view)
        if gens is None:
            return None
        hit = self._cache_get(key, gens, stamp)
        if hit is not None:
            return _looked_up(span, "hit", hit)
        rows = [BSI_EXISTS_BIT, BSI_SIGN_BIT] + [
            BSI_OFFSET_BIT + i for i in range(depth)]
        stale = self._stale_entry(key, gens)
        if stale is not None:
            if self._serve_stale(key, idx.name, field_name, view_name,
                                 shards, stale, gens):
                return _looked_up(span, "stale", stale[1])
            changed = self._changed_shards(stale[0], gens, shards,
                                           rows=len(rows))
            if changed is not None:
                import jax.numpy as jnp

                planes, sign, exists = stale[1]
                host = self._host_rows(
                    view, rows, [shards[j] for j in changed], pad=False)
                with _tracing.start_span("stack.place") as place:
                    block = jnp.asarray(host)
                    jdx = np.asarray(changed)
                    arrays = (
                        self._place(planes.at[:, jdx].set(block[2:]),
                                    shard_axis=1),
                        self._place(sign.at[jdx].set(block[1]),
                                    shard_axis=0),
                        self._place(exists.at[jdx].set(block[0]),
                                    shard_axis=0),
                    )
                    _placed(place, stale[2], "dense")
                self._note_patch("read")
                self._cache_put(key, gens, arrays, stale[2], stamp)
                return _looked_up(span, "patch", arrays,
                                  len(rows) * len(changed))
        arr = self._cold_build(
            view, rows, shards, lambda host: self._place(host, shard_axis=1))
        arrays = (arr[2:], arr[1], arr[0])  # planes, sign, exists
        self._cache_put(key, gens, arrays, arr.size * 4, stamp)
        return _looked_up(span, "build", arrays, len(rows) * len(shards))

    def bsi_condition_stack(self, idx, key, shards):
        """[S, W] mask of a BSI condition leaf evaluated over the cached
        (and incrementally patched) [D, S, W] plane stack in ONE extra
        dispatch — Count(Row(v > 10)) stays O(1)-in-shards (VERDICT r4
        item 4; reference per-shard algorithm fragment.go:1357-1470)."""
        from .bsicond import (
            BsiConditionError,
            apply_bsi_condition,
            bsi_condition_plan,
            condition_from_key,
        )

        _, field_name, op, vals = key
        field = idx.field(field_name)
        if field is None or field.options.type != "int":
            return None
        try:
            plan = bsi_condition_plan(
                field.options, condition_from_key(op, vals))
        except BsiConditionError:
            return None
        # the empty/notnull plans need no magnitude planes (bsicond.py
        # contract) — don't gather+upload the whole [D+2, S, W] stack
        if plan[0] == "empty":
            import jax.numpy as jnp

            return jnp.zeros((self._padded_len(tuple(shards)),
                              WORDS_PER_ROW), dtype=jnp.uint32)
        if plan[0] == "notnull":
            stack = self.rows_stack(idx, field_name, (BSI_EXISTS_BIT,),
                                    tuple(shards),
                                    view_name=field.bsi_view_name())
            return None if stack is None else stack[0]
        data = self.bsi_stack(idx, field_name, shards)
        if data is None:
            return None
        planes, sign, exists = data
        self.dispatches += 1
        with self._locked_dispatch(
                "bsi_condition",
                nbytes_in=(planes.size + sign.size + exists.size) * 4,
                nbytes_out=sign.size * 4) as ph:
            out = apply_bsi_condition(plan, planes, sign, exists)
            ph.mark("dispatch_ack")
            out = _launch_barrier(out)
            ph.mark("sync")
            return out

    def time_row_stack(self, idx, key, shards):
        """[S, W] union of one row across the quantum-view cover (the
        time-range leaf). Each per-view stack is cached + incrementally
        patched like any other; views absent on this holder contribute
        nothing (exactly the executor's per-shard union semantics)."""
        import jax.numpy as jnp

        _, field_name, row_id, views = key
        field = idx.field(field_name)
        if field is None:
            return None
        stacks = []
        for view_name in views:
            if field.view(view_name) is None:
                continue  # no data in this quantum bucket anywhere local
            stack = self.rows_stack(idx, field_name, (row_id,),
                                    tuple(shards), view_name=view_name)
            if stack is None:
                continue  # view vanished mid-query: zero contribution
            stacks.append(stack[0])
        if not stacks:
            return jnp.zeros((self._padded_len(tuple(shards)),
                              WORDS_PER_ROW), dtype=jnp.uint32)
        if len(stacks) == 1:
            return stacks[0]
        # the evaluator's own union fold: one fn-cache, one operator impl
        sig = ("|", tuple(("leaf", i) for i in range(len(stacks))))
        self.dispatches += 1
        fn = self._plane_fn(sig, len(stacks))
        with self._locked_dispatch(
                "time_union",
                nbytes_in=sum(s.size for s in stacks) * 4,
                nbytes_out=stacks[0].size * 4, fn=fn) as ph:
            out = fn(*stacks)
            ph.mark("dispatch_ack")
            out = _launch_barrier(out)
            ph.mark("sync")
            return out

    def row_chunk_size(self, shards):
        """Rows per [R, S, W] chunk under the CHUNK_BYTES budget."""
        return max(
            1, CHUNK_BYTES // (self._padded_len(shards) * WORDS_PER_ROW * 4))

    @contextlib.contextmanager
    def _locked_dispatch(self, kind, nbytes_in=0, nbytes_out=0, fn=None,
                         check_deadline=True):
        """Hold the process-wide dispatch lock around one device launch
        (a count batch's leader: around all of its batch's launches, and
        with `check_deadline` off — every caller of the batch checked
        its own deadline before it queued, and the leader's must not
        fail its followers).

        Always on (a few dict/deque ops a launch; PERF.md section 5
        has what `dispatch.account` costs on the chip): per-kernel
        wall/bytes attribution
        (`kernel_seconds{kernel}` histograms, /debug/kernels), dispatch
        start/end flight-recorder events, and a watchdog op covering the
        lock hold — a dispatch that never returns trips the stall dump
        instead of hanging silently. With a
        QueryProfile active it additionally measures how long THIS query
        waited on the lock vs how long its kernel held it, emits a
        `stacked.kernel` child span (op=kind), and accumulates the
        profile's lock-wait/kernel-wall totals — the two numbers that
        split "slow query" into contention vs compute. The wait for the
        lock is a span of its own, `dispatch.lock_wait`, beside
        `stacked.kernel`, which opens once the lock is held.

        Yields a _PhaseClock: sites mark "dispatch_ack" after the
        program call returns and "sync" after the launch barrier, so a
        dispatch round trip decomposes into where it actually goes
        (GET /debug/dispatch, phase_* profile tags, EXPLAIN ANALYZE
        actuals). `fn` — when it is a _wrap_spec_capture kernel — lets
        the clock detect a first call (its key absent from the arg-spec
        cache) and relabel dispatch_ack as compile."""
        if check_deadline:
            _check_thread_deadline()
        _tracing.end_current("exec.plan")
        prof = _profile.current()
        _flightrec.record("dispatch.start", kernel=kind)
        token = _flightrec.watch_begin("dispatch." + kind)
        compiling = False
        if fn is not None:
            key = getattr(fn, "_spec_key", None)
            compiling = key is not None and key not in self._fn_specs
        t0 = time.perf_counter()
        try:
            with _tracing.start_span("dispatch.lock_wait", op=kind):
                self._dispatch_lock.acquire()
            try:
                t1 = time.perf_counter()
                ph = _PhaseClock(t1, compiling)
                with _tracing.start_span("stacked.kernel",
                                         op=kind) as span:
                    yield ph
                    if span is not None:
                        for phase, dt in ph.phases:
                            span.set_tag(f"phase_{phase}_seconds",
                                         round(dt, 6))
            finally:
                self._dispatch_lock.release()
            t2 = time.perf_counter()
        finally:
            _flightrec.watch_end(token)
        # dispatch.account: the always-on bookkeeping after a launch
        # (kernel and phase tables, their histograms, the flight
        # recorder, the profile's tags), off the lock
        with _tracing.start_span("dispatch.account", op=kind):
            wait, wall = t1 - t0, t2 - t1
            # fold the residual (span bookkeeping, unmarked tails) into
            # the last phase so the phases sum exactly to the dispatch
            # wall; a site that never marked attributes its whole wall in
            # one piece
            if ph.phases:
                ph.phases[-1][1] += t2 - ph._t
            else:
                ph.phases.append(
                    ["compile" if compiling else "dispatch_ack", wall])
            phases = [("lock_wait", wait)] + [tuple(p) for p in ph.phases]
            self._note_kernel(kind, wall, nbytes_in, nbytes_out)
            self._note_phases(kind, phases)
            _flightrec.record("dispatch.end", kernel=kind,
                              lock_wait_seconds=round(wait, 6),
                              kernel_wall_seconds=round(wall, 6))
            if prof is not None:
                prof.add("dispatch_lock_wait_seconds", wait)
                prof.add("kernel_wall_seconds", wall)
                prof.add("locked_dispatches", 1)
                for phase, dt in phases:
                    if phase != "lock_wait":  # already counted above
                        prof.add(f"phase_{phase}_seconds", dt)

    def _note_kernel(self, kind, wall, nbytes_in, nbytes_out):
        """Per-kernel-family attribution (see /debug/kernels)."""
        with self._lock:
            k = self._kernels.get(kind)
            if k is None:
                k = self._kernels[kind] = {
                    "count": 0, "seconds": 0.0,
                    "bytes_in": 0, "bytes_out": 0}
            k["count"] += 1
            k["seconds"] += wall
            k["bytes_in"] += nbytes_in
            k["bytes_out"] += nbytes_out
        tags = {"kernel": kind}
        global_stats.timing("kernel_seconds", wall, tags)
        if nbytes_in:
            global_stats.count("kernel_bytes_in", nbytes_in, tags)
        if nbytes_out:
            global_stats.count("kernel_bytes_out", nbytes_out, tags)

    def _note_phases(self, kind, phases):
        """Per-kernel per-phase attribution (see GET /debug/dispatch)."""
        with self._lock:
            fam = self._dispatch_phases.get(kind)
            if fam is None:
                fam = self._dispatch_phases[kind] = {}
            for phase, dt in phases:
                p = fam.get(phase)
                if p is None:
                    p = fam[phase] = {"count": 0, "seconds": 0.0}
                p["count"] += 1
                p["seconds"] += dt
        for phase, dt in phases:
            global_stats.timing("dispatch_phase_seconds", dt,
                                {"kernel": kind, "phase": phase})

    def dispatch_phases(self):
        """{kernel: {phase: {count, seconds}}} snapshot — the RTT
        decomposition behind GET /debug/dispatch and the analyze path's
        per-phase before/after delta basis. Phase seconds other than
        lock_wait sum to the family's kernel wall by construction."""
        with self._lock:
            return {k: {p: dict(v) for p, v in fam.items()}
                    for k, fam in self._dispatch_phases.items()}

    # -- compiled kernels ----------------------------------------------------

    def _cached_fn(self, key):
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
            return fn

    def _cache_fn(self, key, fn):
        with self._lock:
            self._fns[key] = fn
            while len(self._fns) > MAX_FNS:
                self._fns.popitem(last=False)

    def _get_fn(self, key, build):
        fn = self._cached_fn(key)
        if fn is None:
            fn = self._wrap_spec_capture(key, build())
            self._cache_fn(key, fn)
        return fn

    def _wrap_spec_capture(self, key, fn):
        """Record the arg shape specs on a compiled fn's FIRST call (one
        dict-membership check afterwards), so /debug/kernels can lower +
        compile for jax cost_analysis() lazily — the flops/bytes numbers
        come from XLA, but never at serving-path cost."""
        def wrapped(*args):
            if key not in self._fn_specs:
                try:
                    import jax

                    self._fn_specs[key] = tuple(
                        jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for a in args)
                except Exception:  # noqa: BLE001 — attribution only
                    self._fn_specs[key] = None
            return fn(*args)

        wrapped._jit_fn = fn
        wrapped._spec_key = key  # first-call (compile) detection
        return wrapped

    @staticmethod
    def _tree_eval(sig, stacks):
        if sig[0] == "leaf":
            return stacks[sig[1]]
        op, subs = sig
        acc = StackedEvaluator._tree_eval(subs[0], stacks)
        for s in subs[1:]:
            p = StackedEvaluator._tree_eval(s, stacks)
            if op == "&":
                acc = acc & p
            elif op == "|":
                acc = acc | p
            elif op == "^":
                acc = acc ^ p
            else:
                acc = acc & ~p
        return acc

    def _count_fn(self, sig, csig):
        """Tree -> (hi, lo) int32 popcount totals over all shards.
        `csig` is the tuple of container signatures (or a legacy arity
        int meaning that many raw dense stacks — test/back-compat call
        sites). The program itself lives in ops/containers.count_program:
        all-dense signatures trace to EXACTLY the legacy tree-eval +
        popcount program (to_dense is the identity), which is the
        forced-dense bit-identity guarantee."""
        csig = _containers.norm_csig(csig)

        def build():
            return _named_jit(
                "count_tree", lambda *flat: _containers.count_program(
                    sig, csig, flat, self._tree_eval))

        return self._get_fn(("count", sig, csig), build)

    def _count_batch_fn(self, shape, csig, size):
        """`size` independent count trees of one SHAPE (_split_root) in
        ONE program: args are `ops` int32[size], each slot's root
        operator as data, then size*flat_arity container components;
        outputs are [size] (hi, lo) vectors. A slot switches on its code
        between the whole count programs of the four operators —
        count_program on the concrete signature, popcount and (hi, lo)
        split included, so each branch is the fusion the solo program
        runs and keeps its own strategy — and the spare slot's branch,
        which touches no operand. A bare leaf has the one count branch;
        lax.switch clamps PAD_OP to the last branch, the spare one.
        Returns the jitted program itself; what serves is its compilation
        for one group's shapes (_compile_count_bucket)."""
        import jax
        import jax.numpy as jnp

        csig = _containers.norm_csig(csig)
        af = _containers.flat_arity(csig)
        sigs = [shape] if shape[0] == "leaf" else [
            (op, shape[1]) for op in ROOT_OPS]

        def count(sig):
            return lambda *flat: _containers.count_program(
                sig, csig, flat, self._tree_eval)

        def spare(*flat):
            zero = jnp.zeros((), jnp.int32)
            return zero, zero

        branches = [count(sig) for sig in sigs] + [spare]

        def fn(ops, *all_flat):
            his, los = [], []
            for q in range(size):
                hi, lo = jax.lax.switch(
                    ops[q], branches, *all_flat[q * af:(q + 1) * af])
                his.append(hi)
                los.append(lo)
            return jnp.stack(his), jnp.stack(los)

        return _named_jit("count_batch", fn)

    def fused_count_fn(self, plans):
        """A whole query's Count trees fused into ONE program (exec/
        fusion.py). `plans` is a tuple of (sig, csig) per top-level
        call — unlike _count_batch_fn the trees need NOT share a
        signature; each call's components are sliced off the flat
        argument list by its own arity and traced through its own
        count_program, so the fused program inlines dense, sparse, RLE
        and overlay-carrying containers side by side. Outputs are
        [n_calls] (hi, lo) vectors — the same 16-bit overflow-split
        contract as every count program."""
        import jax.numpy as jnp

        plans = tuple((sig, _containers.norm_csig(csig))
                      for sig, csig in plans)
        key = ("fused", plans)

        def build():
            def fn(*all_flat):
                his, los = [], []
                i = 0
                for sig, csig in plans:
                    af = _containers.flat_arity(csig)
                    hi, lo = _containers.count_program(
                        sig, csig, all_flat[i:i + af], self._tree_eval)
                    i += af
                    his.append(hi)
                    los.append(lo)
                return jnp.stack(his), jnp.stack(los)

            return _named_jit("fused_count", fn)

        return self._get_fn(key, build), key

    def fused_count(self, plans, stacks_per_call):
        """Execute a whole query's Count calls as ONE locked dispatch +
        one group-committed fetch. Returns (counts, fn_key, compiled):
        per-call host ints in call order, the program's fn-cache key
        (exec/fusion.py pins it so its LRU eviction can drop the
        compiled fn too), and whether THIS invocation traced+compiled
        (first call on the key — same detection _locked_dispatch uses
        to relabel dispatch_ack as compile)."""
        fn, key = self.fused_count_fn(plans)
        compiled = key not in self._fn_specs
        args, nbytes_in = [], 0
        for stacks in stacks_per_call:
            args.extend(_containers.flatten(stacks))
            nbytes_in += sum(c.nbytes for c in stacks)
        self.dispatches += 1
        with self._lock:
            self.fused_dispatches += 1
        with self._locked_dispatch("fused", nbytes_in=nbytes_in,
                                   fn=fn) as ph:
            his, los = fn(*args)
            ph.mark("dispatch_ack")
            _launch_barrier((his, los))
            ph.mark("sync")
        # amortized result fetch (group commit, like _batched_count)
        vals = self._fetch_commit.submit((his, los), _device_get_batch)
        his_h, los_h = np.atleast_1d(vals[0]), np.atleast_1d(vals[1])
        counts = [combine_hi_lo(h, l) for h, l in zip(his_h, los_h)]
        return counts, key, compiled

    #: largest fused count program: 32 bounds device time per launch (32
    #: passes over the leaf stacks) near the round trip it amortizes. A
    #: group goes out as ONE launch of the next power of two, its spare
    #: slots reading nothing (_launch_plan), so at most log2(MAX) bucket
    #: programs compile per (shape, containers, argument shapes)
    MAX_COUNT_BATCH = 32

    def _batched_count(self, sig, stacks):
        """Group-commit count execution: the batch leader takes every
        count query that queued during the previous batch, groups them
        by program (shape, containers and stack shapes — the root
        operator is data), launches each group under ONE hold of the
        dispatch lock, fetches ALL results in one transfer, and
        distributes. A lone query leads at once and pays nothing extra;
        a follower makes no launch, takes no lock and fetches nothing;
        leader failures propagate to every waiter (GroupCommit contract).

        The caller looked its stacks up itself, before it queues here: a
        read that follows an acknowledged write is answered from stacks
        looked up after the ack, whoever leads its batch.

        The per-payload return is (count, fused-batch size); the size is
        stamped into the waiter's thread-local here so SLOW QUERY lines
        and strategy notes can attribute `batch=` without threading it
        through every caller."""
        _check_thread_deadline()
        count, size = self._count_commit.submit(
            (sig, tuple(stacks)), self._process_count_batch)
        note_batch_size(size)
        return count

    def _process_count_batch(self, payloads):
        """GroupCommit `process` for count queries: payloads are
        (sig, stacks) pairs; returns (count, fused-batch size) pairs in
        order — the size is how many queries shared the payload's
        launch. Every query reads its own leaves (flat arguments, one
        count_program a slot, as _count_batch_fn builds them); a spare
        slot is handed its group's first leaves and reads none."""
        groups = {}
        nbytes_in = 0
        for pos, (sig, stacks) in enumerate(payloads):
            flat = _containers.flatten(stacks)
            shape, op = _split_root(sig)
            # a group is one program: the dense csig carries no shape,
            # and a compiled bucket takes one shape and sharding only
            key = (shape, tuple(c.csig for c in stacks),
                   tuple((a.shape, a.dtype, a.sharding) for a in flat))
            groups.setdefault(key, []).append((pos, sig, op, flat))
            nbytes_in += sum(c.nbytes for c in stacks)
        launches = []  # (program, arguments, positions answered)
        unbuilt = []
        spare = 0
        for key, members in groups.items():
            at = 0
            for slots, n in _launch_plan(len(members), self.MAX_COUNT_BATCH):
                fn = None if slots == 1 else self._cached_fn(
                    ("countB", key, slots))
                if fn is not None or slots == 1:
                    cover = [(fn, slots, n)]
                else:
                    # never compile with followers waiting: send the
                    # chunk in the programs that exist, build afterwards
                    unbuilt.append((key, slots))
                    cover = self._built_cover(key, n)
                for fn, size, real in cover:
                    chunk = members[at:at + real]
                    at += real
                    positions = [pos for pos, _, _, _ in chunk]
                    if fn is None:  # one slot: the solo program
                        _, sig, _, flat = chunk[0]
                        launches.append(
                            (self._count_fn(sig, key[1]), flat, positions))
                    else:
                        spare += size - real
                        launches.append(
                            (fn, _bucket_args(chunk, size), positions))
        first = next((fn for fn, _, _ in launches
                      if fn._spec_key not in self._fn_specs), None)
        with self._locked_dispatch("count", nbytes_in=nbytes_in, fn=first,
                                   check_deadline=False) as ph:
            outs = [fn(*args) for fn, args, _ in launches]
            ph.mark("dispatch_ack")
            _launch_barrier(outs)
            ph.mark("sync")
            span = _tracing.current_span()
            if span is not None:
                span.set_tag("queries", len(payloads))
                span.set_tag("launches", len(launches))
        with self._lock:
            self.count_launches += len(launches)
            self.count_pad_slots += spare
            self.count_batch_fallbacks += len(unbuilt)
        vals = fetch([a for out in outs for a in out])  # ONE transfer
        results = [None] * len(payloads)
        for i, (_, _, positions) in enumerate(launches):
            # atleast_1d: the solo program returns 0-d scalars
            his = np.atleast_1d(vals[2 * i])
            los = np.atleast_1d(vals[2 * i + 1])
            for q, pos in enumerate(positions):
                results[pos] = (combine_hi_lo(his[q], los[q]),
                                len(positions))
        for key, slots in unbuilt:
            self._build_count_bucket(key, slots)
        return results

    def _built_cover(self, key, n):
        """(program, slots, queries) launches that send `n` queries of
        group `key` while the bucket they ask for is not built: the
        smallest built bucket that holds them all, else the largest
        built one as often as it fills and the rest likewise; solos
        (no program, one slot) where none is built or one query is
        left."""
        built = [(slots, fn) for slots, fn in (
            (1 << k, self._cached_fn(("countB", key, 1 << k)))
            for k in range(1, self.MAX_COUNT_BATCH.bit_length()))
            if fn is not None]
        cover = []
        while built and n > 1:
            slots, fn = next((b for b in built if b[0] >= n), built[-1])
            cover.append((fn, slots, min(n, slots)))
            n -= min(n, slots)
        return cover + [(None, 1, 1)] * n

    def _build_count_bucket(self, key, size):
        """Compile the `size`-slot program of group `key` on a thread
        of its own: off the dispatch lock, with nobody waiting on it.
        One build a bucket; a failed build goes to the flight recorder
        and the bucket's chunks keep going out in the programs that
        exist."""
        fkey = ("countB", key, size)
        with self._lock:
            if fkey in self._count_builds or fkey in self._fns:
                return
            thread = self._count_builds[fkey] = threading.Thread(
                target=self._compile_count_bucket, args=(fkey,),
                name="count-bucket-build", daemon=True)
        thread.start()

    def _compile_count_bucket(self, fkey):
        import jax

        _, (shape, csig, leaves), size = fkey
        specs = (jax.ShapeDtypeStruct((size,), np.int32),) + tuple(
            jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
            for dims, dtype, sharding in leaves) * size
        try:
            jitted = self._count_batch_fn(shape, csig, size)
            compiled = jitted.lower(*specs).compile()
        except Exception as exc:  # noqa: BLE001 — the solos keep serving
            _flightrec.record("count_bucket.build_failed", size=size,
                              sig=repr(shape), error=repr(exc))
            return
        fn = self._wrap_spec_capture(fkey, compiled)
        fn._jit_fn = jitted
        self._fn_specs[fkey] = specs
        self._cache_fn(fkey, fn)
        with self._lock:
            del self._count_builds[fkey]

    def _plane_fn(self, sig, csig):
        """Tree -> combined [S, W] plane stack (filter materialization).
        Compressed leaves decompress in-program (exact by construction)
        so the output is always the legacy dense plane; `csig` accepts a
        legacy arity int for raw dense args (time_union fold)."""
        csig = _containers.norm_csig(csig)

        def build():
            return _named_jit(
                "plane_tree", lambda *flat: _containers.plane_program(
                    sig, csig, flat, self._tree_eval))

        return self._get_fn(("plane", sig, csig), build)

    def _row_counts_fn(self, has_filt):
        """(rows [R,S,W], filt [S,W]?) -> (hi [R], lo [R]) counts of
        rows ∩ filter over all shards."""
        import jax
        import jax.numpy as jnp

        def build():
            def counts(rows, filt):
                x = rows & filt[None] if has_filt else rows
                per_shard = jnp.sum(
                    jax.lax.population_count(x).astype(jnp.int32), axis=-1)
                return bitplane.hi_lo(per_shard, axis=-1)

            if has_filt:
                return _named_jit("row_counts", counts)
            return _named_jit("row_counts", lambda rows: counts(rows, None))

        return self._get_fn(("row_counts", has_filt), build)

    def _sum_fn(self, has_filt):
        """(planes [D,S,W], sign, exists, filt?) -> per-plane positive and
        negative popcounts + consider count, all as (hi, lo) pairs
        (reference: fragment.sum fragment.go:1068)."""
        import jax
        import jax.numpy as jnp

        def build():
            def kernel(planes, sign, exists, filt):
                consider = exists & filt if has_filt else exists
                pos = consider & ~sign
                neg = consider & sign
                pc = jnp.sum(jax.lax.population_count(
                    planes & pos[None]).astype(jnp.int32), axis=-1)  # [D,S]
                nc = jnp.sum(jax.lax.population_count(
                    planes & neg[None]).astype(jnp.int32), axis=-1)
                cc = jnp.sum(jax.lax.population_count(
                    consider).astype(jnp.int32), axis=-1)            # [S]
                return (*bitplane.hi_lo(pc, axis=-1),
                        *bitplane.hi_lo(nc, axis=-1),
                        *bitplane.hi_lo(cc))

            if has_filt:
                return _named_jit("bsi_sum", kernel)
            return _named_jit(
                "bsi_sum", lambda planes, sign, exists: kernel(
                    planes, sign, exists, None))

        return self._get_fn(("sum", has_filt), build)

    def _minmax_fn(self, has_filt, is_max):
        """One-dispatch global Min/Max over stacked BSI planes.

        Computes both the positive-branch and negative-branch narrowing
        walks (ops.bsi min/max_unsigned work unchanged on [D,S,W] planes
        with [S,W] filters — the scans are elementwise with global any())
        and selects per the reference's sign rules (fragment.go:1110-1227):
        Max: highest positive else closest-to-zero negative; Min: most
        negative else lowest positive. Returns (empty, use_neg, bits [D],
        cnt_hi, cnt_lo)."""
        import jax
        import jax.numpy as jnp

        from ..ops import bsi as bsi_ops

        def build():
            def kernel(planes, sign, exists, filt):
                consider = exists & filt if has_filt else exists
                pos = consider & ~sign
                neg = consider & sign
                has_pos = jnp.any(pos != 0)
                has_neg = jnp.any(neg != 0)
                empty = ~(has_pos | has_neg)
                if is_max:
                    # highest positive, else closest-to-zero negative
                    b_pos, f_pos = bsi_ops.max_unsigned(planes, pos)
                    b_neg, f_neg = bsi_ops.min_unsigned(planes, neg)
                    use_neg = ~has_pos
                else:
                    # most negative, else lowest positive
                    b_neg, f_neg = bsi_ops.max_unsigned(planes, neg)
                    b_pos, f_pos = bsi_ops.min_unsigned(planes, pos)
                    use_neg = has_neg
                bits = jnp.where(use_neg, b_neg, b_pos)
                final = jnp.where(use_neg, f_neg, f_pos)
                per_shard = jnp.sum(
                    jax.lax.population_count(final).astype(jnp.int32),
                    axis=-1)
                return (empty, use_neg, bits, *bitplane.hi_lo(per_shard))

            if has_filt:
                return _named_jit("bsi_minmax", kernel)
            return _named_jit(
                "bsi_minmax", lambda planes, sign, exists: kernel(
                    planes, sign, exists, None))

        return self._get_fn(("minmax", has_filt, is_max), build)

    # -- public entry points -------------------------------------------------

    def _gather(self, idx, call, shards):
        """Shared tree-coverage + leaf-stack gather: (sig, stacks) or None
        when the tree isn't stack-coverable or a leaf's field vanished
        (concurrent DDL) — callers fall back to the per-shard path."""
        leaves = {}
        sig = self.signature(idx, call, leaves)
        if sig is None or not leaves:
            return None
        ordered = sorted(leaves.items(), key=lambda kv: kv[1])
        stacks = []
        for key, _ in ordered:
            if key[0] == "bsicond":
                s = self.bsi_condition_stack(idx, key, shards)
            elif key[0] == "timerow":
                s = self.time_row_stack(idx, key, shards)
            else:
                _, field_name, row_id = key
                # leaf_stack returns a Container already
                stacks.append(
                    self.leaf_stack(idx, field_name, row_id, shards))
                continue
            # bsi-condition masks / time-union folds are freshly computed
            # dense planes: wrap without copying so downstream programs
            # see one uniform container argument shape
            stacks.append(
                None if s is None else _containers.dense_container(s))
        if any(s is None for s in stacks):
            return None
        return sig, stacks

    def try_count(self, idx, call_child, shards):
        """Count(call_child) over `shards` in one dispatch, or None when
        the tree isn't coverable (caller falls back)."""
        shards = tuple(shards)
        if len(shards) < MIN_SHARDS:
            return None
        gathered = self._gather(idx, call_child, shards)
        if gathered is None:
            return None
        sig, stacks = gathered
        self.dispatches += 1
        # group-commit execution: concurrent count queries fuse into one
        # program + one result round trip (see _batched_count)
        return self._batched_count(sig, stacks)

    def filter_stack(self, idx, call, shards):
        """Materialize a bitmap call tree as one [S, W] device stack.
        Returns (covered, stack): covered=False means the tree has shapes
        the stacked path can't express (fall back to per-shard);
        stack=None with covered=True means "no filter given"."""
        if call is None:
            return True, None
        shards = tuple(shards)
        gathered = self._gather(idx, call, shards)
        if gathered is None:
            return False, None
        sig, stacks = gathered
        self.dispatches += 1
        fn = self._plane_fn(sig, tuple(c.csig for c in stacks))
        plane_bytes = stacks[0].shape[0] * stacks[0].shape[1] * 4
        with self._locked_dispatch(
                "filter",
                nbytes_in=sum(c.nbytes for c in stacks),
                nbytes_out=plane_bytes, fn=fn) as ph:
            out = fn(*_containers.flatten(stacks))
            ph.mark("dispatch_ack")
            out = _launch_barrier(out)
            ph.mark("sync")
            return True, out

    def row_counts(self, idx, field_name, row_ids, filt, shards,
                   view_name=VIEW_STANDARD):
        """{row_id: exact count of row ∩ filt summed over shards}, in
        O(rows/chunk) dispatches independent of the shard count. `filt` is
        a [S, W] device stack from filter_stack (or None). Returns None
        when the field/view vanished mid-query."""
        shards = tuple(shards)
        out = {}
        chunk_size = self.row_chunk_size(shards)
        # Oversized candidate sets can't all stay resident: build those
        # chunks transiently instead of churning out every cached chunk.
        total_bytes = (len(row_ids) * self._padded_len(shards)
                       * WORDS_PER_ROW * 4)
        cache = total_bytes <= budgets()[1]
        fn = self._row_counts_fn(filt is not None)
        pending = []
        import jax

        for i in range(0, len(row_ids), chunk_size):
            chunk = tuple(row_ids[i:i + chunk_size])
            stack = self.rows_stack(idx, field_name, chunk, shards,
                                    view_name, cache=cache)
            if stack is None:
                return None
            self.dispatches += 1
            n_in = stack.size * 4 + (filt.size * 4 if filt is not None
                                     else 0)
            with self._locked_dispatch("row_counts", nbytes_in=n_in,
                                       fn=fn) as ph:
                hi_lo = fn(stack, filt) if filt is not None else fn(stack)
                ph.mark("dispatch_ack")
                _launch_barrier(hi_lo)
                if not cache:
                    # Transient chunks: block before building the next one
                    # so peak HBM stays ~CHUNK_BYTES instead of the whole
                    # candidate set queued in flight.
                    jax.block_until_ready(hi_lo)
                ph.mark("sync")
            pending.append((chunk, hi_lo))
        # ONE amortized fetch for every chunk's (hi, lo) pair — shared
        # with concurrently-serving queries via the group commit
        flat = tuple(a for _, hl in pending for a in hl)
        if flat:
            vals = self._fetch_commit.submit(flat, _device_get_batch)
            for k, (chunk, _) in enumerate(pending):
                totals = combine_hi_lo(vals[2 * k], vals[2 * k + 1])
                for j, row_id in enumerate(chunk):
                    out[row_id] = int(totals[j])
        return out

    def pairwise_counts(self, idx, a_field, a_rows, b_field, b_rows, filt,
                        shards, view_name=VIEW_STANDARD, tile=None):
        """{(a_row, b_row): count > 0} of the two-field GroupBy cross
        product: counts[i, j] = popcount(a_rows[i] & b_rows[j] & filt)
        summed over `shards`. Both fields' row stacks come from the rows
        pool ([R, S, W], incrementally patched like any chunk); the
        [tile, tile] count matrix is ONE fused dispatch and ONE host sync
        per (A-tile, B-tile) pair — O(⌈R1/tile⌉·⌈R2/tile⌉) round trips
        total, vs the recursive path's one `row_counts` sync per A row.
        The sync rides the group commit, so concurrent GroupBys (and any
        Sum/Min/Max traffic) share round trips. `tile` overrides the
        static CHUNK_BYTES-derived shape (the adaptive tile decision);
        per-dispatch walls feed back into the engine's per-tile EWMA.
        Returns None when a field/view vanished mid-query (caller falls
        back)."""
        shards = tuple(shards)
        out = {}
        if not a_rows or not b_rows:
            return out
        if tile is None or tile < 1:
            tile = self.row_chunk_size(shards)
        observe = _adaptive.enabled()
        row_bytes = self._padded_len(shards) * WORDS_PER_ROW * 4
        rows_budget = budgets()[1]
        cache_a = len(a_rows) * row_bytes <= rows_budget
        cache_b = len(b_rows) * row_bytes <= rows_budget
        import jax

        for i in range(0, len(a_rows), tile):
            a_chunk = tuple(a_rows[i:i + tile])
            a_stack = self.rows_stack(idx, a_field, a_chunk, shards,
                                      view_name, cache=cache_a)
            if a_stack is None:
                return None
            for j in range(0, len(b_rows), tile):
                b_chunk = tuple(b_rows[j:j + tile])
                b_stack = self.rows_stack(idx, b_field, b_chunk, shards,
                                          view_name, cache=cache_b)
                if b_stack is None:
                    return None
                self.dispatches += 1
                self.pairwise_dispatches += 1
                n_in = (a_stack.size + b_stack.size
                        + (filt.size if filt is not None else 0)) * 4
                t_disp = time.perf_counter() if observe else 0.0
                with self._locked_dispatch(
                        "pairwise", nbytes_in=n_in,
                        nbytes_out=len(a_chunk) * len(b_chunk) * 8) as ph:
                    hi, lo = bitplane.pairwise_counts_hi_lo(
                        a_stack, b_stack, filt)
                    ph.mark("dispatch_ack")
                    _launch_barrier((hi, lo))
                    if not (cache_a and cache_b):
                        # Transient tiles: bound peak HBM before the next
                        # pair (same discipline as row_counts).
                        jax.block_until_ready((hi, lo))
                    ph.mark("sync")
                if observe:
                    # calibrate per-dispatch wall at the NOMINAL tile —
                    # ragged last tiles blend in, which is fine: the
                    # model prices whole shapes, not individual tiles
                    _adaptive.observe_pairwise(
                        tile, time.perf_counter() - t_disp)
                # ONE host sync for the whole [tile, tile] matrix, shared
                # with concurrent serving traffic via the group commit
                vals = self._fetch_commit.submit((hi, lo),
                                                 _device_get_batch)
                self.pairwise_syncs += 1
                totals = combine_hi_lo(vals[0], vals[1])
                for x, y in zip(*np.nonzero(totals)):
                    out[(a_chunk[x], b_chunk[y])] = int(totals[x, y])
        return out

    def try_sum(self, idx, field, filter_call, shards):
        """(signed magnitude total, count) for Sum over stacked BSI planes,
        or None to fall back. The caller adds base*count (field.go:1583)."""
        shards = tuple(shards)
        if len(shards) < MIN_SHARDS:
            return None
        covered, filt = self.filter_stack(idx, filter_call, shards)
        if not covered:
            return None
        data = self.bsi_stack(idx, field.name, shards)
        if data is None:
            return None
        planes, sign, exists = data
        fn = self._sum_fn(filt is not None)
        self.dispatches += 1
        n_in = (planes.size + sign.size + exists.size
                + (filt.size if filt is not None else 0)) * 4
        with self._locked_dispatch("sum", nbytes_in=n_in, fn=fn) as ph:
            if filt is not None:
                res = fn(planes, sign, exists, filt)
            else:
                res = fn(planes, sign, exists)
            ph.mark("dispatch_ack")
            _launch_barrier(res)
            ph.mark("sync")
        p_hi, p_lo, n_hi, n_lo, c_hi, c_lo = \
            self._fetch_commit.submit(tuple(res), _device_get_batch)
        pos = combine_hi_lo(p_hi, p_lo)
        neg = combine_hi_lo(n_hi, n_lo)
        total = 0
        for i in range(planes.shape[0]):
            total += (int(pos[i]) - int(neg[i])) << i
        return total, combine_hi_lo(c_hi, c_lo)

    def try_minmax(self, idx, field, filter_call, shards, is_max):
        """(signed magnitude, count) of the Min/Max value over stacked BSI
        planes, or None to fall back; (None, 0) when no column qualifies.
        The caller adds base (reference: fragment.go:1110-1227)."""
        shards = tuple(shards)
        if len(shards) < MIN_SHARDS:
            return None
        covered, filt = self.filter_stack(idx, filter_call, shards)
        if not covered:
            return None
        data = self.bsi_stack(idx, field.name, shards)
        if data is None:
            return None
        planes, sign, exists = data
        fn = self._minmax_fn(filt is not None, is_max)
        self.dispatches += 1
        n_in = (planes.size + sign.size + exists.size
                + (filt.size if filt is not None else 0)) * 4
        with self._locked_dispatch("minmax", nbytes_in=n_in, fn=fn) as ph:
            if filt is not None:
                res = fn(planes, sign, exists, filt)
            else:
                res = fn(planes, sign, exists)
            ph.mark("dispatch_ack")
            _launch_barrier(res)
            ph.mark("sync")
        # amortized result fetch (group commit, like try_sum)
        empty, use_neg, bits, c_hi, c_lo = \
            self._fetch_commit.submit(tuple(res), _device_get_batch)
        if bool(empty):
            return None, 0
        bits = np.asarray(bits)
        mag = sum(int(b) << i for i, b in enumerate(bits))
        if bool(use_neg):
            mag = -mag
        return mag, combine_hi_lo(c_hi, c_lo)

    def counters(self):
        """(dispatches, hits, misses, planes_uploaded,
        pairwise_dispatches, pairwise_syncs) — the per-query delta source
        for the always-on workload table and for a query's profile. A
        bare tuple read instead of the full cache_stats() dict: this runs
        twice per query."""
        with self._lock:
            return (self.dispatches, self.hits, self.misses,
                    self.planes_uploaded, self.pairwise_dispatches,
                    self.pairwise_syncs)

    def cache_stats(self):
        """Snapshot for /debug/vars: hit rate and byte pressure reveal
        whether the HBM budgets are thrashing under the live workload.
        `stack_budget_bytes` / `rows_stack_budget_bytes` are the budgets
        in force (`budgets()`: shares of the device's memory, or the
        constants where it reports none); `builds`, `build_seconds` and
        `build_gather_seconds` say what a miss costs and where."""
        stack_budget, rows_budget = budgets()
        with self._lock:
            return {
                "stack_budget_bytes": stack_budget,
                "rows_stack_budget_bytes": rows_budget,
                "builds": self.builds,
                "build_seconds": self.build_seconds,
                "build_gather_seconds": self.build_gather_seconds,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "patches": self.patches,
                "stale_serves": self.stale_serves,
                "planes_uploaded": self.planes_uploaded,
                "dispatches": self.dispatches,
                "pairwise_dispatches": self.pairwise_dispatches,
                "pairwise_syncs": self.pairwise_syncs,
                "group_fetches": self._fetch_commit.batches,
                "group_fetched_queries": self._fetch_commit.batched,
                "count_batches": self._count_commit.batches,
                "count_batched_queries": self._count_commit.batched,
                "count_launches": self.count_launches,
                "count_pad_slots": self.count_pad_slots,
                "count_batch_fallbacks": self.count_batch_fallbacks,
                "fused_dispatches": self.fused_dispatches,
                "stack_bytes": self._stack_bytes,
                "stack_entries": len(self._stacks),
                "rows_stack_bytes": self._rows_stack_bytes,
                "rows_stack_entries": len(self._rows_stacks),
                "evictions_by_cause": {
                    f"{p}.{c}": n
                    for (p, c), n in sorted(self.pool_evictions.items())},
            }

    def invalidate(self):
        with self._lock:
            n_stack = len(self._stacks)
            n_rows = len(self._rows_stacks)
            self._stacks.clear()
            self._stack_bytes = 0
            self._rows_stacks.clear()
            self._rows_stack_bytes = 0
            # zero (don't drop) the gauges: a scraper must see the flush
            for (index, field, pool_name, repr_kind) in list(self._hbm_ledger):
                global_stats.gauge("hbm_stack_bytes", 0, {
                    "index": index, "field": field, "pool": pool_name,
                    "repr": repr_kind})
            self._hbm_ledger.clear()
            if n_stack:
                self._count_eviction("stack", "invalidate", n_stack)
            if n_rows:
                self._count_eviction("rows", "invalidate", n_rows)
        if n_stack or n_rows:
            _flightrec.record("cache.invalidate", stack_entries=n_stack,
                              rows_entries=n_rows)

    # -- HBM / kernel attribution (GET /debug/hbm, /debug/kernels) -----------

    def hbm_snapshot(self, top=50):
        """What is resident in HBM and for whom: per-(index, field, pool)
        byte attribution, the resident entries ranked by bytes with
        last-hit age, eviction causes, and headroom vs the device's own
        memory_stats(). `total_bytes` is EXACTLY
        _stack_bytes + _rows_stack_bytes (the ledger moves in lockstep
        under the same lock — the acceptance stress test asserts it)."""
        now = time.time()
        entries = []
        stack_budget, rows_budget = budgets()
        with self._lock:
            for pool_name, pool in (("stack", self._stacks),
                                    ("rows", self._rows_stacks)):
                for key, entry in pool.items():
                    e = {
                        "pool": pool_name,
                        "kind": key[0],
                        "index": key[1],
                        "field": key[2],
                        "bytes": entry[2],
                        "repr": _containers.kind_of(entry[1]),
                        "last_hit_age_seconds": round(now - entry[4], 3),
                        "key": repr(key),
                    }
                    if isinstance(entry[1], _containers.Container):
                        ratio = entry[1].meta.get("ratio")
                        if ratio is not None:
                            e["compression_ratio"] = ratio
                    entries.append(e)
            # aggregate the repr-keyed ledger back to (index, field,
            # pool) for by_index_field consumers (the /debug/heat join
            # keys on index+field), and expose the repr split + the
            # per-representation totals alongside
            agg = {}
            by_repr = {}
            for (i, f, p, r), b in self._hbm_ledger.items():
                agg[(i, f, p)] = agg.get((i, f, p), 0) + b
                by_repr[r] = by_repr.get(r, 0) + b
            by_index_field = [
                {"index": i, "field": f, "pool": p, "bytes": b}
                for (i, f, p), b in sorted(
                    agg.items(), key=lambda kv: -kv[1])]
            by_index_field_repr = [
                {"index": i, "field": f, "pool": p, "repr": r, "bytes": b}
                for (i, f, p, r), b in sorted(
                    self._hbm_ledger.items(), key=lambda kv: -kv[1])]
            snap = {
                "total_bytes": self._stack_bytes + self._rows_stack_bytes,
                "stack_bytes": self._stack_bytes,
                "stack_entries": len(self._stacks),
                "stack_budget_bytes": stack_budget,
                "rows_stack_bytes": self._rows_stack_bytes,
                "rows_stack_entries": len(self._rows_stacks),
                "rows_stack_budget_bytes": rows_budget,
                "device_bytes_limit": _device.memory_bytes(),
                "by_index_field": by_index_field,
                "by_index_field_repr": by_index_field_repr,
                "by_repr": by_repr,
                "container_fragments": _containers.fragment_ledger(),
                "evictions": {
                    f"{p}.{c}": n
                    for (p, c), n in sorted(self.pool_evictions.items())},
            }
        entries.sort(key=lambda e: -e["bytes"])
        snap["entries"] = entries[:top]
        snap["device_memory"] = self._device_memory()
        return snap

    def _device_memory(self):
        """Per-device memory_stats() headroom, with the RuntimeMonitor
        guard: NEVER initializes a backend (jax absent or uninitialized
        -> None), and backends without memory_stats report nothing."""
        if not _device.backends_are_initialized():
            return None
        import jax

        try:
            out = []
            for d in jax.local_devices():
                ms = getattr(d, "memory_stats", None)
                stats = ms() if callable(ms) else None
                if not stats:
                    continue
                in_use = stats.get("bytes_in_use")
                limit = stats.get("bytes_limit")
                dev = {"device": str(d.id), "platform": d.platform}
                if in_use is not None:
                    dev["bytes_in_use"] = int(in_use)
                if limit is not None:
                    dev["bytes_limit"] = int(limit)
                    if in_use is not None:
                        dev["headroom_bytes"] = int(limit) - int(in_use)
                out.append(dev)
            return out or None
        except Exception:  # noqa: BLE001 — observability must not raise
            return None

    def kernels_snapshot(self, include_costs=True):
        """Per-kernel-family attribution (counts, wall seconds, bytes
        in/out from _locked_dispatch) plus XLA cost_analysis (flops /
        bytes accessed) per compiled program — computed ONCE per fn on
        the first /debug/kernels request, never on the serving path."""
        with self._lock:
            kernels = {k: dict(v) for k, v in self._kernels.items()}
        snap = {"kernels": kernels}
        if include_costs:
            snap["compiled"] = self._kernel_cost_list()
        return snap

    def _kernel_cost_list(self):
        with self._lock:
            specs = dict(self._fn_specs)
            fns = dict(self._fns)
        out = []
        for key, spec in specs.items():
            cost = self._kernel_costs.get(key)
            if cost is None:
                cost = self._cost_analysis(fns.get(key), spec)
                with self._lock:
                    self._kernel_costs[key] = cost
            out.append({"family": str(key[0]), "key": repr(key),
                        "cost": cost})
        out.sort(key=lambda e: e["key"])
        return out

    @staticmethod
    def _cost_analysis(fn, specs):
        """XLA's own flops/bytes estimate for one compiled program: the
        dict `Compiled.cost_analysis()` returns, cut to the totals
        /debug/kernels shows ({} for a program XLA prices nothing on)."""
        if fn is None or not specs:
            return {}
        cost = fn._jit_fn.lower(*specs).compile().cost_analysis()
        return {k: cost[k]
                for k in ("flops", "bytes accessed", "optimal_seconds",
                          "transcendentals") if k in cost}

    # -- plan-mode introspection (exec/plan.py) ------------------------------
    #
    # EXPLAIN mirrors the strategy gates WITHOUT executing: everything
    # below is host-only (schema lookups, fragment generation walks, pool
    # membership under the lock) and side-effect free — no LRU bumps, no
    # hit/miss counters, no stack builds, no dispatches. The acceptance
    # contract for ?explain=true is a dispatch-counter delta of zero.

    def _probe(self, key, idx, field_name, view_name):
        """Presence + freshness of one pool entry with NO side effects
        (see _probe_entry)."""
        return self._probe_entry(key, idx, field_name, view_name)[0]

    def _probe_entry(self, key, idx, field_name, view_name):
        """(resident, resident_bytes, repr) of one pool entry with NO
        side effects. Mirrors _cache_get_fast/_cache_get validation
        (view stamp first, per-shard generation walk second) but never
        touches LRU order, last-hit stamps, or the hit/miss counters —
        a plan must not distort the telemetry it is trying to explain.
        bytes/repr are the RESIDENT entry's (compressed container bytes
        for compressed leaf stacks); (0, "dense") when absent."""
        field = idx.field(field_name)
        view = field.view(view_name) if field is not None else None
        if view is None:
            return False, 0, "dense"
        pool, _ = self._pool(key)
        row_id = key[3] if key[0] == "leaf" else None
        with self._lock:
            hit = pool.get(key)
            if hit is None:
                return False, 0, "dense"
            if hit[3] == view.stamp(row_id):
                return True, hit[2], _containers.kind_of(hit[1])
        # stamp drifted: fall back to the exact generation walk (done
        # outside the pool lock — it touches fragment containers)
        gens = self._fragment_gens(idx, field_name, key[-1], view_name,
                                   view=view, row_id=row_id)
        if gens is None:
            return False, 0, "dense"
        with self._lock:
            hit = pool.get(key)
            if hit is not None and hit[0] == gens:
                return True, hit[2], _containers.kind_of(hit[1])
            return False, 0, "dense"

    def rows_chunk_resident(self, idx, field_name, row_chunk, shards,
                            view_name=VIEW_STANDARD):
        """Would rows_stack() serve this chunk from the rows pool?"""
        key = ("rows", idx.name, field_name, view_name, tuple(row_chunk),
               tuple(shards))
        return self._probe(key, idx, field_name, view_name)

    def bsi_stack_resident(self, idx, field_name, shards):
        """Would bsi_stack() serve this field's plane stack from HBM?"""
        field = idx.field(field_name)
        if field is None:
            return False
        key = ("bsi", idx.name, field_name, field.options.bit_depth,
               tuple(shards))
        return self._probe(key, idx, field_name, field.bsi_view_name())

    def residency_probe(self, idx, call, shards):
        """Host-only coverage + HBM residency of a bitmap call tree:

        {covered, leaves, resident, resident_bytes, missing_bytes,
         extra_kernels}

        covered mirrors _gather's verdict (same signature walk); per
        interned leaf the probe reports whether its device stack(s) are
        already resident and how many bytes a cold build would upload.
        extra_kernels counts dispatches _gather itself would issue on
        top of the consumer's own kernel (bsi_condition masks,
        time_union folds) so estimates don't undercount BSI/time trees."""
        shards = tuple(shards)
        out = {"covered": False, "leaves": 0, "resident": 0,
               "resident_bytes": 0, "missing_bytes": 0,
               "extra_kernels": {}, "repr_counts": {},
               "compressed_bytes": 0}
        leaves = {}
        sig = self.signature(idx, call, leaves)
        if sig is None or not leaves:
            return out
        out["covered"] = True
        out["leaves"] = len(leaves)
        plane = self._padded_len(shards) * WORDS_PER_ROW * 4
        for key in leaves:
            # per-leaf representation + compressed-bytes estimate for
            # the cost model: actual container bytes when resident, the
            # fragment ledger's last-build record when not (the chooser
            # is deterministic in the data, so the last build predicts
            # the next), dense otherwise. resident/missing_bytes keep
            # their dense meaning — they price the HOST gather a cold
            # build pays, which is dense either way.
            ckind, cbytes = "dense", None
            if key[0] == "bsicond":
                resident, nbytes = self._probe_bsicond(idx, key, shards,
                                                       plane, out)
            elif key[0] == "timerow":
                resident, nbytes = self._probe_timerow(idx, key, shards,
                                                       plane, out)
            else:
                _, field_name, row_id = key
                leaf_key = ("leaf", idx.name, field_name, row_id, shards)
                resident, ebytes, ekind = self._probe_entry(
                    leaf_key, idx, field_name, VIEW_STANDARD)
                nbytes = plane
                if resident:
                    ckind, cbytes = ekind, ebytes
                else:
                    est = _containers.fragment_estimate(
                        idx.name, field_name, VIEW_STANDARD, row_id)
                    if est is not None:
                        ckind, cbytes = est["repr"], est["bytes"]
            rc = out["repr_counts"]
            rc[ckind] = rc.get(ckind, 0) + 1
            out["compressed_bytes"] += cbytes if cbytes is not None \
                else nbytes
            if resident:
                out["resident"] += 1
                out["resident_bytes"] += nbytes
            else:
                out["missing_bytes"] += nbytes
        return out

    def _probe_bsicond(self, idx, key, shards, plane, out):
        """(resident, cold_bytes) of one condition leaf; counts the
        bsi_condition dispatch the gather would add."""
        from .bsicond import (
            BsiConditionError,
            bsi_condition_plan,
            condition_from_key,
        )

        _, field_name, op, vals = key
        field = idx.field(field_name)
        if field is None:
            return False, 0
        try:
            plan = bsi_condition_plan(
                field.options, condition_from_key(op, vals))
        except BsiConditionError:
            return False, 0
        if plan[0] == "empty":
            return True, 0  # constant zeros, nothing uploaded
        if plan[0] == "notnull":
            return self.rows_chunk_resident(
                idx, field_name, (BSI_EXISTS_BIT,), shards,
                view_name=field.bsi_view_name()), plane
        ek = out["extra_kernels"]
        ek["bsi_condition"] = ek.get("bsi_condition", 0) + 1
        depth = field.options.bit_depth
        return (self.bsi_stack_resident(idx, field_name, shards),
                (depth + 2) * plane)

    def _probe_timerow(self, idx, key, shards, plane, out):
        """(resident, cold_bytes) of one time-range leaf: one cached
        single-row chunk per locally-present quantum view, plus a
        time_union dispatch when more than one contributes."""
        _, field_name, row_id, views = key
        field = idx.field(field_name)
        if field is None:
            return False, 0
        present = [v for v in views if field.view(v) is not None]
        if len(present) > 1:
            ek = out["extra_kernels"]
            ek["time_union"] = ek.get("time_union", 0) + 1
        resident = all(
            self.rows_chunk_resident(idx, field_name, (row_id,), shards,
                                     view_name=v)
            for v in present)
        return resident, len(present) * plane

    def kernel_profile(self):
        """Per-family dispatch counters snapshot ({family: {count,
        seconds, bytes_in, bytes_out}}) — the cost model's "measured"
        source and the analyze path's before/after delta basis."""
        with self._lock:
            return {k: dict(v) for k, v in self._kernels.items()}


# Backwards-compatible name (the evaluator originally covered Count only).
StackedCountEvaluator = StackedEvaluator

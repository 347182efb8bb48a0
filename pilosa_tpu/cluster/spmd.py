"""Pod-scale SPMD data plane: cross-node query merge over collectives.

The reference merges cross-node partial results over HTTP/protobuf
(executor.remoteExec executor.go:2414, http/client.go:268) — the
coordinator POSTs per-node shard lists and sums JSON/proto responses. In
SPMD mode that data plane is replaced by the accelerator fabric: every
server process joins ONE global JAX distributed system
(`jax.distributed.initialize` — gloo across CPU hosts, ICI/DCN collectives
on TPU pods), each query leaf materializes as a single globally-sharded
[shards, words] array whose per-process blocks come from that node's own
fragments, and one jit-compiled program runs on every process in lockstep —
XLA inserts the cross-process all-reduce, so merges ride the fabric instead
of JSON over REST. Covered merges: Count, Sum, Min/Max, TopN, GroupBy —
every cross-node aggregate the reference reduces (executor.go:925-1237).

HTTP remains the CONTROL plane (SURVEY §2 "distributed communication
backend": control over DCN, data merge over ICI): the cluster coordinator
announces each step via POST /internal/spmd/step, every process (including
the coordinator) executes the identical program, and the replicated scalar
result is read locally — no result bytes cross HTTP.

Execution model (multi-controller SPMD):
- Only the cluster coordinator node initiates steps, and it serializes
  them under a local lock; peer processes execute steps from their HTTP
  handler thread under the same per-process lock. With a single initiator
  this yields an identical step order on every process — the requirement
  for collectives to rendezvous correctly.
- Queries arriving at NON-coordinator nodes forward eligible calls to the
  coordinator in one internal hop (POST /internal/spmd/initiate) so every
  node serves the collective path — matching the reference, where any node
  coordinates the merge (executor.Execute executor.go:113) — while step
  initiation stays single-sourced.
- Steps carry a FULLY-RESOLVED plan (operator signature + leaf list,
  candidate rows, bit depth): peers never re-derive signatures from their
  own possibly-racing schema. Combined with defensive block gathering
  (anything missing locally contributes zero planes — count-neutral for
  every covered op), a peer that validated CANNOT fail to enter the
  collective, which closes the validate-to-collective wedge window (a peer
  raising before the jitted program runs would block the coordinator
  inside the step with the lock held).
- Steps are gated on every node being READY: a process that never joins a
  collective would hang the others, so degraded clusters fall back to the
  HTTP path (which has per-replica retry).

Count totals use the framework-wide (hi, lo) int32 split reduce
(ops.bitplane.hi_lo) — exact past 2^31 bits without x64.

Mesh observatory (PR 19): every process runs a per-step phase clock
(_StepClock, mirroring the PR-6 dispatch _PhaseClock contract:
residual-folded so per-phase seconds sum EXACTLY to the step wall) and
records each step into a bounded ring. The coordinator assembles the
rings into one skew-corrected cross-node timeline
(GET /debug/spmd/steps) with per-phase straggler attribution — the
evidence layer the spmd_never_entered / spmd_collective_hung wedge
classes were missing.
"""

import itertools
import statistics
import threading
import time
from collections import OrderedDict, deque

import numpy as np

from ..core.view import VIEW_BSI_GROUP_PREFIX, VIEW_STANDARD
from ..pql import Call, call_to_pql, parse
from ..shardwidth import WORDS_PER_ROW
from ..utils.logger import NopLogger


class SpmdError(Exception):
    pass


# -- plan wire encoding -------------------------------------------------------

def sig_to_wire(sig):
    """Operator signature -> JSON-able nested lists (steps carry the plan
    so every process evaluates the IDENTICAL program; see module doc)."""
    if sig is None:
        return None
    if sig[0] == "leaf":
        return ["leaf", sig[1]]
    op, subs = sig
    return [op, [sig_to_wire(s) for s in subs]]


def sig_from_wire(wire):
    if wire is None:
        return None
    if wire[0] == "leaf":
        return ("leaf", int(wire[1]))
    return (wire[0], tuple(sig_from_wire(s) for s in wire[1]))


# -- mesh observatory ---------------------------------------------------------

#: step-phase taxonomy (GET /debug/spmd/steps; docs/architecture.md):
#: announce_recv — announcement receipt to collective entry (stream-queue
#: wait + step-lock wait on peers; fan-out time on the coordinator);
#: stack_gather — host fragment gather + make_array_from_process_local_data
#: for every leaf/BSI/row stack; device_enter — the jitted collective
#: program call returning its (possibly async) output handles; psum —
#: block_until_ready on those handles, i.e. the collective rendezvous +
#: execution (a straggling peer shows up HERE on everyone else); result_
#: fetch — device-to-host conversion of the replicated outputs; exit —
#: residual-folded terminal phase (decode + lifecycle bookkeeping), which
#: absorbs the fold so the phases sum EXACTLY to the step wall.
STEP_PHASES = ("announce_recv", "stack_gather", "device_enter", "psum",
               "result_fetch", "exit")

#: padding buckets of a `count_batch` step: a fused query's K plans are
#: padded up to the next bucket (repeating plan 0), so at most
#: len(BATCH_BUCKETS) collective programs compile per signature set
BATCH_BUCKETS = (1, 4, 16, 64)


def batch_bucket(n):
    """Smallest padding bucket holding `n` plans."""
    for b in BATCH_BUCKETS:
        if n <= b:
            return b
    return BATCH_BUCKETS[-1]


class _StepClock:
    """Phase marks within one collective step — the PR-6 _PhaseClock
    contract (exec/stacked.py) lifted to the step plane: `mark(phase)`
    attributes the time since the previous mark (or the announcement
    receipt) to `phase`; `close()` folds any residual into the terminal
    phase so the per-phase seconds sum EXACTLY to the step wall."""

    __slots__ = ("t0", "_t", "phases")

    def __init__(self, t0=None):
        now = time.perf_counter()
        self.t0 = self._t = now if t0 is None else t0
        self.phases = []

    def mark(self, phase):
        now = time.perf_counter()
        self.phases.append([phase, now - self._t])
        self._t = now

    def close(self, phase="exit"):
        """Fold the residual into `phase` and return the step wall."""
        self.mark(phase)
        return self._t - self.t0


def envelope_skew(t_send, t_recv, remote_now):
    """NTP-style clock-offset estimate (remote - local, seconds) from one
    RPC envelope: the peer stamped `remote_now` (its wall clock) while
    handling a request we sent at local wall time `t_send` and answered
    at `t_recv`. Assuming symmetric network delay (the same assumption
    as tracing.estimate_skew, which derives theta from span pairs), the
    remote stamp corresponds to the local midpoint of the envelope."""
    return remote_now - (t_send + t_recv) / 2.0


def attribute_stragglers(peers_phases, factor, noise_floor):
    """Per-phase straggler attribution for ONE step's merged per-peer
    phase walls. `peers_phases`: {node_id: {phase: seconds}}. A node is
    the phase's straggler when its wall is the slowest AND exceeds the
    median of the OTHER peers by `factor` (excluding the candidate —
    on a 2-node mesh a median over both would dilute the straggler's
    own wall into the baseline) AND by more than `noise_floor` seconds
    in absolute terms (so microsecond jitter between healthy peers
    never flags). Returns [{phase, node, seconds, median_seconds,
    ratio}]."""
    flags = []
    phases = set()
    for ph in peers_phases.values():
        phases.update(ph)
    for phase in sorted(phases):
        walls = {node: ph[phase] for node, ph in peers_phases.items()
                 if phase in ph}
        if len(walls) < 2:
            continue
        worst_node = max(walls, key=walls.get)
        worst = walls[worst_node]
        med = statistics.median(v for n, v in walls.items()
                                if n != worst_node)
        if worst > med * factor and worst - med > noise_floor:
            flags.append({
                "phase": phase,
                "node": worst_node,
                "seconds": round(worst, 6),
                "median_seconds": round(med, 6),
                "ratio": round(worst / med, 2) if med > 0 else None,
            })
    return flags


#: the serving process's data plane (set by cli.cmd_server) — what the
#: incident-autopsy `spmd` collector snapshots into EVERY postmortem
#: bundle without holding an instance handle (utils/incident.py)
_active_plane = None


def set_active_plane(plane):
    global _active_plane
    _active_plane = plane
    return plane


def active_plane():
    return _active_plane


def observatory_snapshot():
    """Incident-bundle collector payload: the active plane's full
    observatory state (step ring + phase tables + a best-effort
    cross-node timeline), or the disabled stub."""
    plane = _active_plane
    if plane is None:
        return {"enabled": False}
    try:
        return dict(plane.incident_snapshot(), enabled=True)
    except Exception as e:  # noqa: BLE001 — never fail the bundle
        return {"enabled": True, "error": str(e)}


class SpmdDataPlane:
    #: process-wide init guard (jax.distributed.initialize is once-only)
    _initialized = False

    @classmethod
    def initialize(cls, coordinator_address, num_processes, process_id,
                   cpu_collectives=None):
        """Join the global JAX distributed system. MUST run before any JAX
        backend initializes in this process — cli.cmd_server calls it
        ahead of utils/device.boot, the first backend use.

        cpu_collectives="gloo" opts the CPU backend into real
        cross-process collectives (the 2-process CPU harness and any
        gloo-capable CPU cluster); without it multi-process CPU programs
        raise "Multiprocess computations aren't implemented on the CPU
        backend". Must be set before the backend initializes, same as the
        distributed init itself."""
        if cls._initialized:
            return
        import jax

        if cpu_collectives == "gloo":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id)
        cls._initialized = True

    #: seconds a step announcement may block (first-query jit compile +
    #: collective rendezvous on a cold pod can far exceed the default 30s)
    STEP_TIMEOUT = 300
    #: seconds for the cheap pre-flight validation round
    VALIDATE_TIMEOUT = 5
    #: compiled-program cache bound (mirrors exec.stacked.MAX_FNS: tiny
    #: functions, but unbounded distinct shapes would accumulate)
    MAX_FNS = 128
    #: serve-mode values settable at runtime (POST /debug/spmd). "http"
    #: is runtime-only: it forces maybe_execute to decline so the SAME
    #: cluster can run the HTTP fan-out path for an A/B bench comparison.
    SERVE_MODES = ("off", "on", "shadow", "http")
    #: seconds a peer's stream runner waits on a sequence gap before
    #: resyncing to the lowest queued step (a lost announcement must not
    #: wedge the stream forever; the coordinator's collective for the
    #: lost step fails via the distributed-runtime timeout and falls back)
    STREAM_GAP_TIMEOUT = 30
    #: bounded per-node step ring (mesh observatory): most recent steps
    #: with per-phase walls, what GET /debug/spmd/steps merges cross-node
    STEP_RING_SIZE = 256
    #: a node is a phase's straggler when its wall exceeds the peer
    #: median by this factor AND by STRAGGLER_NOISE_FLOOR seconds in
    #: absolute terms (2x of a 50us gather is jitter, not a straggler)
    STRAGGLER_FACTOR = 2.0
    STRAGGLER_NOISE_FLOOR = 0.025
    #: edge-trigger memory: (seq, node, phase) keys already counted /
    #: flightrec'd, so repeated GET /debug/spmd/steps scrapes of the same
    #: ring don't re-fire events (bounded FIFO)
    STRAGGLER_FLAGS_MAX = 1024

    def __init__(self, holder, cluster, client_factory, logger=None,
                 serve_mode="off", stream_gap_timeout=None):
        self.holder = holder
        self.cluster = cluster
        self.client_factory = client_factory
        self.logger = logger or NopLogger()
        self._lock = threading.Lock()  # one step at a time per process
        self._mesh = None
        self._fns = OrderedDict()
        self._step_id = 0
        # --spmd-serve: "off" keeps the pre-mesh data plane byte-identical
        # (no cache, blocking step announcements); "on" enables the
        # mesh-resident cache + step-stream + batched/fused steps;
        # "shadow" serves legacy while probing the cache for divergence.
        self.serve_mode = serve_mode if serve_mode in self.SERVE_MODES \
            else "off"
        from .meshstacks import MeshStackCache

        self.mesh_cache = MeshStackCache(logger=self.logger)
        # step-stream control plane (serve_mode == "on"): peers execute
        # announced steps in sequence order from a runner thread instead
        # of the announcing HTTP handler thread, so the coordinator can
        # pipeline announcement N+1 while step N executes.
        self._stream_cond = threading.Condition()
        self._stream_queue = {}  # seq -> step
        self._stream_next = None  # next seq to execute (set by first recv)
        self._stream_thread = None
        self._stream_closed = False
        # outbound stream sequence: SEPARATE from _step_id so legacy-mode
        # steps (serve off/shadow) never open gaps in the stream — a gap
        # costs the peer a STREAM_GAP_TIMEOUT resync stall
        self._stream_seq_out = 0
        self.stream_errors = 0
        self.stream_resyncs = 0
        # --spmd-stream-gap-timeout override (satellite: a 30s silent
        # stall was invisible until resync; ops can now shorten the fuse)
        if stream_gap_timeout is not None and stream_gap_timeout > 0:
            self.STREAM_GAP_TIMEOUT = float(stream_gap_timeout)
        # -- mesh observatory state ------------------------------------
        # Separate lock from self._lock: the whole point of the step ring
        # is reading it WHILE a collective is wedged holding _lock.
        self._obs_lock = threading.Lock()
        self._step_ring = deque(maxlen=self.STEP_RING_SIZE)
        self._phase_totals = {}  # phase -> [count, seconds]
        # the in-flight step's clock; only the step-executing thread
        # writes it (one step at a time per process under _lock)
        self._step_clock = None
        # last completed step record, thread-local: the coordinator's
        # query thread IS its step-executing thread, so ANALYZE/profile
        # grafting reads its own step's phases race-free under load
        self._step_tls = threading.local()
        self.gap_onsets = 0
        self.gap_stall_seconds = 0.0
        self._straggler_flags = OrderedDict()  # (seq, node, phase) -> 1
        self.straggler_flags_total = 0
        # per-node step lifecycle counters (satellite: wedge root-cause —
        # announced>entered means a peer never reached the collective,
        # entered>exited means the collective itself hung)
        self.steps_announced = 0
        self.steps_entered = 0
        self.steps_exited = 0
        self.last_seq = 0
        # batched/fused collective accounting
        self.batch_steps = 0
        self.fused_steps = 0
        self.fused_queries = 0
        # Count pre-flight epochs: {index: membership epoch} of the last
        # successful validation round. Steps carry resolved plans, so the
        # per-query peer checks are all membership/boot-constant — one
        # validation round per epoch suffices (steady-state count = ONE
        # HTTP round per query). Node state changes form a new epoch.
        self._count_epochs = OrderedDict()
        # observability: /internal/spmd/stats
        self.steps_run = 0
        self.validations = 0
        self.validations_skipped = 0
        self.forwarded = 0
        self.forward_errors = 0
        self.fallbacks = 0  # eligible calls declined past the gate (caps…)
        self._local_exec = None  # set by API (shared serving executor)
        # The JAX process set is fixed at startup (initialize is
        # once-only); if the cluster later grows or shrinks, SPMD must
        # decline — new nodes are not mesh participants.
        self._boot_node_ids = tuple(sorted(n.id for n in cluster.nodes)) \
            if cluster is not None else ()

    # -- mesh ----------------------------------------------------------------

    def _global_sharding(self, shard_axis=0, ndim=2):
        """NamedSharding over the GLOBAL device list, process-major, so
        each process's addressable block is contiguous along the shard
        axis (what make_array_from_process_local_data fills)."""
        if self._mesh is None:
            from ..parallel.sharded import build_global_mesh

            self._mesh = build_global_mesh()
        import jax

        spec = [None] * ndim
        spec[shard_axis] = "shards"
        return jax.sharding.NamedSharding(
            self._mesh, jax.sharding.PartitionSpec(*spec))

    def _local_device_count(self):
        import jax

        return len(jax.local_devices())

    def _num_processes(self):
        import jax

        return jax.process_count()

    def mesh_shape(self):
        """(processes, devices per process) — the mesh-key component and
        the shape EXPLAIN reports."""
        return [self._num_processes(), self._local_device_count()]

    def set_serve_mode(self, mode):
        """Runtime serve-mode switch (POST /debug/spmd). Raises on an
        unknown mode; the caller maps that to a 400."""
        if mode not in self.SERVE_MODES:
            raise SpmdError(f"unknown spmd serve mode: {mode!r}")
        self.serve_mode = mode
        return self.serve_mode

    # -- signature helper ----------------------------------------------------

    def _signature(self, idx, call):
        """Tree signature for SPMD coverage (coordinator side only — the
        resolved plan ships IN the step). Same shape rules as the stacked
        evaluator (shared walk: exec.stacked.tree_signature) but leaf
        checks consult only REPLICATED state (the schema): local
        view/fragment existence differs per node, and a node that owns no
        shards of a field simply contributes zero planes."""
        from ..exec.bsicond import normalize_bsi_condition
        from ..exec.stacked import tree_signature

        def leaf(idx, field_name, row_id, leaves):
            if idx.field(field_name) is None:
                return None
            key = ("row", field_name, int(row_id))
            if key not in leaves:
                leaves[key] = len(leaves)
            return ("leaf", leaves[key])

        def bsi_leaf(idx, field_name, cond, leaves):
            field = idx.field(field_name)
            if field is None or field.options.type != "int":
                return None
            norm = normalize_bsi_condition(cond)
            if norm is None:
                return None
            op, vals = norm
            key = ("bsicond", field_name, op, vals)
            if key not in leaves:
                leaves[key] = len(leaves)
            return ("leaf", leaves[key])

        from ..exec.stacked import intern_time_leaf

        leaves = {}
        sig = tree_signature(idx, call, leaves, leaf, bsi_leaf,
                             intern_time_leaf)
        if sig is None or not leaves:
            return None
        ordered = sorted(leaves.items(), key=lambda kv: kv[1])
        return sig, [key for key, _ in ordered]

    @staticmethod
    def _leaf_to_wire(key):
        """Leaf key -> JSON-able tagged entry: ["row", field, row_id] or
        ["bsicond", field, op, values]."""
        if key[0] == "bsicond":
            _, field_name, op, vals = key
            return ["bsicond", field_name, op,
                    list(vals) if isinstance(vals, tuple) else vals]
        if key[0] == "timerow":
            _, field_name, row_id, views = key
            return ["timerow", field_name, row_id, list(views)]
        _, field_name, row_id = key
        return ["row", field_name, row_id]

    def _plan_filter(self, idx, step, filter_call):
        """Attach an optional filter plan to a step; False when the filter
        tree isn't coverable (caller falls back to HTTP)."""
        if filter_call is None:
            step["sig"] = None
            step["leaves"] = []
            return True
        sig_leaves = self._signature(idx, filter_call)
        if sig_leaves is None:
            return False
        sig, leaf_keys = sig_leaves
        step["sig"] = sig_to_wire(sig)
        step["leaves"] = [self._leaf_to_wire(k) for k in leaf_keys]
        return True

    # -- entry (any node) ----------------------------------------------------

    def _call_kind(self, call):
        if call.name == "Count" and len(call.children) == 1:
            return "count"
        if call.name == "Sum":
            return "sum"
        if call.name == "TopN":
            return "topn"
        if call.name in ("Min", "Max"):
            return "minmax"
        if call.name == "GroupBy":
            return "groupby"
        return None

    def maybe_execute(self, idx, call, shards, forwarded=False):
        """THE ClusterExecutor entry: (used, result). used=False means the
        caller should take the HTTP merge path. Runs on ANY node: the
        coordinator initiates directly; other nodes forward eligible calls
        to the coordinator in one hop (reference: any node coordinates,
        executor.go:113)."""
        if self.serve_mode == "http":
            return False, None  # bench A/B: force the HTTP fan-out path
        kind = self._call_kind(call)
        if kind is None:
            return False, None
        cluster = self.cluster
        if cluster is None or len(cluster.nodes) < 2:
            return False, None
        from .node import NODE_STATE_READY

        if any(n.state != NODE_STATE_READY for n in cluster.nodes):
            return False, None  # a hung participant would stall the mesh
        if tuple(sorted(n.id for n in cluster.nodes)) != self._boot_node_ids:
            return False, None  # membership changed since distributed init
        coord = cluster.coordinator
        if coord is None:
            return False, None
        if coord.id != cluster.local_id:
            if forwarded:
                return False, None  # never bounce a forwarded call again
            # schema-level pre-check so a call the coordinator would
            # refuse anyway never pays the forward hop (the coordinator
            # itself skips this: its _try_* handlers re-derive the same
            # signatures as part of building the step plan)
            if not self._eligible(idx, call, kind):
                return False, None
            return self._forward(idx, call, shards, coord)
        try_fn = {
            "count": self._try_count,
            "sum": self._try_sum,
            "topn": self._try_topn,
            "minmax": self._try_minmax,
            "groupby": self._try_groupby,
        }[kind]
        from ..utils import tracing

        before = getattr(self._step_tls, "rec", None)
        try:
            # the collective data plane is otherwise invisible to a query
            # profile — this span records that the query went over SPMD
            # (and how long the collective step took) instead of HTTP
            with tracing.start_span("spmd.step", kind=kind,
                                    shards=len(shards)) as span:
                result = try_fn(idx, call, list(shards))
                self._graft_span(span, before=before)
        except Exception as e:
            # Watchdog: a wedged/failed collective (e.g. a peer that died
            # inside the amortized-validation window while still marked
            # READY) surfaces here once the distributed runtime times out.
            # Invalidate the epoch so the next query re-probes peers, and
            # fall back to the HTTP merge instead of erroring the query.
            self.fallbacks += 1
            self._count_epochs.pop(idx.name, None)
            self.logger.printf(
                "spmd: %s step failed (%s); epoch invalidated, falling "
                "back to HTTP merge", kind, e)
            return False, None
        if result is None:
            return False, None
        return True, result

    def _eligible(self, idx, call, kind):
        """Replicated-schema eligibility shared by the forward pre-check
        and the coordinator: every check here depends only on state all
        nodes agree on, so a non-coordinator can decline locally instead
        of paying a wasted hop for a call the coordinator would refuse."""
        if kind == "count":
            return self._signature(idx, call.children[0]) is not None
        if kind in ("sum", "minmax"):
            if self._agg_field(idx, call, want_int=True) is None:
                return False
            filter_call = call.children[0] if call.children else None
            return filter_call is None \
                or self._signature(idx, filter_call) is not None
        if kind == "topn":
            field_name = call.args.get("_field") or call.field_arg()
            field = idx.field(field_name) if field_name else None
            if field is None or field.options.type == "int":
                return False
            if call.args.get("tanimotoThreshold") \
                    or call.args.get("attrName") is not None \
                    or call.args.get("ids") is not None \
                    or len(call.children) > 1:
                return False
            filter_call = call.children[0] if call.children else None
            return filter_call is None \
                or self._signature(idx, filter_call) is not None
        if kind == "groupby":
            from ..core.field import FIELD_TYPE_INT, FIELD_TYPE_TIME

            if not call.children:
                return False
            for child in call.children:
                if child.name != "Rows":
                    return False
                if "column" in child.args or "from" in child.args \
                        or "to" in child.args:
                    return False
                fname = child.args.get("_field") \
                    or child.args.get("field") or child.field_arg()
                field = idx.field(fname) if fname else None
                if field is None or field.type in (FIELD_TYPE_INT,
                                                   FIELD_TYPE_TIME):
                    return False
            filter_call = call.args.get("filter")
            if filter_call is None:
                return True
            return isinstance(filter_call, Call) \
                and self._signature(idx, filter_call) is not None
        return False

    def _forward(self, idx, call, shards, coord):
        """Non-coordinator hop: hand the eligible call to the coordinator
        for step initiation (single initiator keeps step order global)."""
        try:
            client = self.client_factory(coord.uri)
            client.timeout = self.STEP_TIMEOUT + 30
            resp = client.spmd_initiate({
                "index": idx.name,
                "pql": call_to_pql(call),
                "shards": list(shards),
            })
        except Exception as e:
            self.forward_errors += 1
            self.logger.printf(
                "spmd: initiate forward to coordinator failed "
                "(falling back to HTTP merge): %s", e)
            return False, None
        if not resp.get("used"):
            return False, None
        self.forwarded += 1
        from .executor import result_from_json

        return True, result_from_json(resp.get("result"))

    def initiate(self, payload):
        """Coordinator-side handler for POST /internal/spmd/initiate."""
        idx = self.holder.index(payload["index"])
        if idx is None:
            return {"used": False}
        call = parse(payload["pql"]).calls[0]
        used, result = self.maybe_execute(
            idx, call, [int(s) for s in payload["shards"]], forwarded=True)
        if not used:
            return {"used": False}
        return {"used": True, "result": self._wire_result(result)}

    @staticmethod
    def _wire_result(result):
        from ..exec.result import GroupCount, Pair, ValCount

        if isinstance(result, ValCount):
            return result.to_json()
        if isinstance(result, list):
            if result and isinstance(result[0], (Pair, GroupCount)):
                return [r.to_json() for r in result]
            return list(result)
        return int(result)  # count

    # -- coordinator gating --------------------------------------------------

    def _gate(self, idx, shards):
        """Shard-segment skeleton for a step (padding so every process
        contributes an equal-shaped block). Cluster-health checks live in
        maybe_execute; this only derives shapes."""
        cluster = self.cluster
        by_node = cluster.shards_by_node(idx.name, list(shards))
        segments = {node.id: sorted(s) for node, s in by_node.items()}
        # every process contributes an equal-shaped block (zero planes for
        # nodes with fewer/no shards), padded to its device multiple
        dev_pp = self._local_device_count()
        longest = max((len(s) for s in segments.values()), default=0)
        seg_len = max(dev_pp, ((longest + dev_pp - 1) // dev_pp) * dev_pp)
        return {
            "index": idx.name,
            "segments": segments,
            "seg_len": seg_len,
            "dev_pp": dev_pp,
            "nodes": list(self._boot_node_ids),
        }

    def _execute_step(self, step):
        """Announce + run one validated step (coordinator side).

        Legacy (serve != on): blocking POST /internal/spmd/step per peer,
        joined around the local collective — byte-identical to the
        pre-mesh control plane.

        Streamed (serve == on): fire-and-ack POST /internal/spmd/stream —
        the peer enqueues the step by sequence number and acks before
        executing, so this call returns as soon as the LOCAL collective
        completes and the coordinator can announce step N+1 while a slow
        peer is still inside step N (the collective itself is the
        synchronization; the old blocking join double-paid it in HTTP
        round-trip time)."""
        from ..utils import flightrec, tracing

        streamed = self.serve_mode == "on"
        # carry the coordinator's trace id so every node's step record —
        # and the merged /debug/spmd/steps timeline — joins back to the
        # query (?profile=true span graft, --metrics-exemplars buckets)
        span = tracing.current_span()
        if span is not None and "trace" not in step:
            step["trace"] = span.trace_id
        with self._lock:
            # announce_recv t0 on the coordinator: announcement fan-out +
            # own step-lock wait (peers overwrite with their receipt time)
            step["_recv_t"] = time.perf_counter()
            self._step_id += 1
            step["step"] = self._step_id
            if streamed:
                self._stream_seq_out += 1
                step["seq"] = self._stream_seq_out
            self.steps_announced += 1
            flightrec.record(
                "spmd.step_announce", index=step.get("index", ""),
                op=step.get("kind", "count"),
                seq=step.get("seq", self._step_id), streamed=streamed)
            errors = []

            def post(node):
                try:
                    client = self.client_factory(node.uri)
                    client.timeout = self.STEP_TIMEOUT
                    if streamed:
                        client.spmd_stream(step)
                    else:
                        client.spmd_step(step)
                except Exception as e:  # surfaced after the collective
                    errors.append((node.id, e))

            threads = [threading.Thread(target=post, args=(n,),
                                        daemon=True)
                       for n in self.cluster.peers()]
            for t in threads:
                t.start()
            # join the collective ourselves — peers are inside run_step
            # (legacy) or their stream runner (streamed) now
            result = self._enter_exit_run(step)
            if not streamed:
                for t in threads:
                    t.join()
        if streamed:
            # acks raced the collective; collect without holding the lock
            for t in threads:
                t.join(timeout=self.VALIDATE_TIMEOUT)
        if errors:
            # We hold a replicated result: for validated-this-query steps
            # every process joined the collective and these are
            # post-collective transport errors (lost responses / lost
            # stream acks). For epoch-skipped count steps a dead peer
            # instead fails the collective itself, which raises out of
            # _run_step_locked and is handled by the maybe_execute
            # watchdog (epoch invalidated, HTTP fallback). Log, don't
            # fail the query.
            if streamed:
                self.stream_errors += len(errors)
            self.logger.printf(
                "spmd: post-collective peer errors (result kept): %s",
                errors)
        return result

    def _enter_exit_run(self, step):
        """_run_step_locked bracketed by the step-lifecycle flightrec
        events: a node whose recorder shows announce-without-enter never
        reached the collective (control-plane loss); enter-without-exit
        means the collective itself hung. Caller holds self._lock.

        Mesh observatory: runs the step under a _StepClock (t0 = the
        step's announcement-receipt stamp, so announce_recv covers
        stream-queue + lock wait) and under a flightrec watchdog — a
        collective stuck past STEP_TIMEOUT now trips a collective_stall
        incident bundle instead of hanging silently."""
        from ..utils import flightrec

        seq = int(step.get("seq") or step.get("step") or 0)
        kind = step.get("kind", "count")
        started = time.time()
        clk = _StepClock(t0=step.pop("_recv_t", None))
        clk.mark("announce_recv")
        self._step_clock = clk
        self.steps_entered += 1
        self.last_seq = max(self.last_seq, seq)
        flightrec.record("spmd.step_enter", index=step.get("index", ""),
                         op=kind, seq=seq)
        token = flightrec.watch_begin("spmd.step", seq=seq, op=kind,
                                      index=step.get("index", ""))
        ok = False
        try:
            result = self._run_step_locked(step)
            ok = True
            return result
        finally:
            flightrec.watch_end(token)
            self._step_clock = None
            wall = clk.close("exit")
            self.steps_exited += 1
            flightrec.record("spmd.step_exit",
                             index=step.get("index", ""),
                             op=kind, seq=seq,
                             ok=ok)
            self._note_step(step, seq, started, wall, clk.phases, ok)

    def _mark_phase(self, phase):
        """Attribute time-since-last-mark to `phase` on the in-flight
        step's clock (no-op outside a step; the clock is only ever set
        by the thread holding self._lock)."""
        clk = self._step_clock
        if clk is not None:
            clk.mark(phase)

    def _note_step(self, step, seq, started, wall, phase_marks, ok):
        """Fold one finished step into the observatory: the bounded step
        ring + per-phase totals (under _obs_lock so /debug readers never
        touch the step lock) and spmd_step_seconds{phase} timings with
        the step's trace id as the exemplar."""
        phases = {}
        for name, secs in phase_marks:
            phases[name] = phases.get(name, 0.0) + secs
        rec = {
            "seq": seq,
            "step": step.get("step", 0),
            "kind": step.get("kind", "count"),
            "index": step.get("index", ""),
            "start": started,
            "wall_seconds": round(wall, 6),
            "ok": ok,
            "phases": {p: round(s, 6) for p, s in phases.items()},
        }
        trace = step.get("trace")
        if trace:
            rec["trace"] = trace
        with self._obs_lock:
            self._step_ring.append(rec)
            for name, secs in phases.items():
                tot = self._phase_totals.get(name)
                if tot is None:
                    tot = self._phase_totals[name] = [0, 0.0]
                tot[0] += 1
                tot[1] += secs
        self._step_tls.rec = rec
        try:
            from ..utils.stats import global_stats

            for name, secs in phases.items():
                global_stats.timing("spmd_step_seconds", secs,
                                    tags={"phase": name}, trace_id=trace)
            global_stats.timing("spmd_step_wall_seconds", wall,
                                trace_id=trace)
        except Exception:  # noqa: BLE001 — stats must never fail a step
            pass

    def _graft_span(self, span, before=None):
        """Tag the query's spmd.step span with the per-phase walls of
        the step THIS thread just executed, so ?profile=true shows where
        collective wall went. `before` (the thread-local rec prior to
        execution) guards the forwarded case, where no local step ran."""
        if span is None:
            return
        rec = getattr(self._step_tls, "rec", None)
        if rec is None or rec is before:
            return
        span.set_tag("phases_ms", {p: round(s * 1000, 3)
                                   for p, s in rec["phases"].items()})
        span.set_tag("step_seq", rec["seq"])

    def _try_count(self, idx, call, shards):
        """Count(call) merged over the global mesh, or None to fall back
        to the HTTP merge path."""
        sig_leaves = self._signature(idx, call.children[0])
        if sig_leaves is None:
            return None
        step = self._gate(idx, shards)
        sig, leaf_keys = sig_leaves
        step["kind"] = "count"
        step["sig"] = sig_to_wire(sig)
        step["leaves"] = [self._leaf_to_wire(k) for k in leaf_keys]
        # Pre-flight, amortized: the step carries its whole plan, so the
        # per-peer checks (spmd enabled, index present, device count,
        # membership) are constant within a membership epoch — validate
        # once per epoch, not per query (VERDICT r3: steady-state SPMD
        # count costs one HTTP round).
        if not self._ensure_count_epoch(step):
            return None
        return self._execute_step(step)

    def _cluster_ready(self):
        """The maybe_execute_fused cluster gates: coordinator-only (the
        executor calls it on the serving node), every node READY,
        membership unchanged since distributed init."""
        cluster = self.cluster
        if cluster is None or len(cluster.nodes) < 2:
            return False
        from .node import NODE_STATE_READY

        if any(n.state != NODE_STATE_READY for n in cluster.nodes):
            return False
        if tuple(sorted(n.id for n in cluster.nodes)) \
                != self._boot_node_ids:
            return False
        coord = cluster.coordinator
        return coord is not None and coord.id == cluster.local_id

    def _count_plans(self, idx, calls):
        """Wire plans for a list of Count calls, or None when any call
        isn't coverable (the whole query falls back — splitting would
        break the one-announcement contract)."""
        plans = []
        for call in calls:
            if self._call_kind(call) != "count":
                return None
            sig_leaves = self._signature(idx, call.children[0])
            if sig_leaves is None:
                return None
            sig, leaf_keys = sig_leaves
            plans.append({"sig": sig_to_wire(sig),
                          "leaves": [self._leaf_to_wire(k)
                                     for k in leaf_keys]})
        return plans

    # -- fused collective programs (PR-16 fusion x mesh) ---------------------

    def maybe_execute_fused(self, idx, query, shards):
        """Whole multi-call cluster query as ONE fused collective program:
        (used, counts). Gated by the PR-16 fusion admission rules (a cold
        fingerprint never pays a collective compile) and ledgered under
        the mesh-shaped program key, so /debug/fusion shows which fabric
        each collective program was traced for. Warm path: one jitted
        program per process, one announcement, zero result bytes over
        HTTP."""
        from ..exec import fusion as fusion_mod

        if self.serve_mode != "on" or not fusion_mod.acting():
            return False, None
        calls = list(query.calls)
        if not calls or any(self._call_kind(c) != "count" for c in calls):
            return False, None
        if not self._cluster_ready():
            return False, None
        from ..utils import workload as workload_mod

        fp = workload_mod.current_fingerprint()
        if fp is None:
            fp, _ = workload_mod.fingerprint(idx.name, query)
        if not fusion_mod.admit(fp):
            return False, None
        plans = self._count_plans(idx, calls)
        if plans is None:
            return False, None
        step = self._gate(idx, shards)
        step["kind"] = "count_batch"
        k = len(plans)
        bucket = batch_bucket(k)
        # pad to the bucket by repeating plan 0: the mesh cache serves
        # the repeats from device memory
        step["plans"] = plans + [plans[0]] * (bucket - k)
        step["bucket"] = bucket
        if not self._ensure_count_epoch(step):
            return False, None
        sigs = tuple(sig_from_wire(p["sig"]) for p in step["plans"])
        arities = tuple(len(p["leaves"]) for p in step["plans"])
        fn_key = ("count_batch", sigs, arities)
        compiled = fn_key not in self._fns
        import time as _time

        from ..utils import tracing

        t0 = _time.perf_counter()
        try:
            with tracing.start_span("spmd.step", kind="fused",
                                    shards=len(shards), batch=k) as span:
                counts = self._execute_step(step)
                self._graft_span(span)
        except Exception as e:
            self.fallbacks += 1
            self._count_epochs.pop(idx.name, None)
            self.logger.printf(
                "spmd: fused step failed (%s); epoch invalidated, "
                "falling back to per-call path", e)
            return False, None
        wall = _time.perf_counter() - t0
        # ledger AFTER _execute_step released self._lock: fusion eviction
        # re-enters ev._lock (ours) to drop the jitted collective
        key = fusion_mod.mesh_program_key(fp, sigs, bucket,
                                          self.mesh_shape())
        fusion_mod.touch_mesh_program(
            key, self, fn_key,
            compile_ms=wall * 1000 if compiled else None)
        fusion_mod.note_fused(k)
        workload_mod.note_batch(k)
        self.fused_steps += 1
        self.fused_queries += 1
        return True, counts[:k]

    # -- EXPLAIN (plan + analyze) --------------------------------------------

    def plan_eligible(self, idx, call):
        """Would the normal serving path take the collective plane for
        this call? The ?explain=true annotation gate — nothing executes."""
        if self.serve_mode != "on":
            return False
        kind = self._call_kind(call)
        if kind is None:
            return False
        cluster = self.cluster
        if cluster is None or len(cluster.nodes) < 2:
            return False
        from .node import NODE_STATE_READY

        if any(n.state != NODE_STATE_READY for n in cluster.nodes):
            return False
        if tuple(sorted(n.id for n in cluster.nodes)) \
                != self._boot_node_ids:
            return False
        if cluster.coordinator is None:
            return False
        return self._eligible(idx, call, kind)

    def plan_node(self, idx, call, shards):
        """Serialized mesh plan entry for ?explain=true: the collective
        path runs ZERO per-node dispatches from the coordinator's view —
        one globally-sharded program replaces the fan-out."""
        return {
            "op": call.name,
            "strategy": "spmd-collective",
            "annotations": {
                "spmd": True,
                "mesh": self.mesh_shape(),
                "dispatches": 0,
                "shards": len(shards or []),
            },
            "children": [],
        }

    @staticmethod
    def _psum_bytes(kind, result):
        """Replicated all-reduce output payload per process — the bytes
        the collective moved in place of an HTTP result body. Count is
        the (hi, lo) int32 pair; vector kinds scale by output length."""
        if isinstance(result, (list, tuple)):
            return 8 * max(1, len(result))
        return 8

    def maybe_execute_analyze(self, idx, call, shards):
        """?explain=analyze through the collective plane: really execute
        (PR-16 fused-analyze contract: analyze reports the path that
        serves), then graft the step's single dispatch + psum bytes onto
        a mesh plan entry. (used, result, plan_entry)."""
        if self.serve_mode != "on":
            return False, None, None
        import time as _time

        before = getattr(self._step_tls, "rec", None)
        t0 = _time.perf_counter()
        used, result = self.maybe_execute(idx, call, shards)
        if not used:
            return False, None, None
        wall = _time.perf_counter() - t0
        kind = self._call_kind(call)
        entry = {
            "node": "mesh",
            "shards": len(shards or []),
            "plan": {
                "op": call.name,
                "strategy": "spmd-collective",
                "annotations": {
                    "spmd": True,
                    "mesh": self.mesh_shape(),
                    "dispatches": 1,
                    "psum_bytes": self._psum_bytes(kind, result),
                    "wall_ms": round(wall * 1000, 3),
                },
                "children": [],
            },
        }
        # mesh observatory: this thread just executed the coordinator's
        # half of the step (the query thread IS the step thread), so its
        # thread-local step record carries the per-phase walls — graft
        # them under the collective node's annotations. `rec is before`
        # means no local step ran (the call was forwarded): skip.
        rec = getattr(self._step_tls, "rec", None)
        if rec is not None and rec is not before:
            entry["plan"]["annotations"]["phases_ms"] = {
                p: round(s * 1000, 3) for p, s in rec["phases"].items()}
            entry["plan"]["annotations"]["step_seq"] = rec["seq"]
        return True, result, entry

    def _membership_epoch(self):
        return tuple((n.id, n.state) for n in self.cluster.nodes)

    def _ensure_count_epoch(self, step):
        epoch = self._membership_epoch()
        if self._count_epochs.get(step["index"]) == epoch:
            self.validations_skipped += 1
            return True
        if self._validate_on_peers(step) is None:
            return False
        self._count_epochs[step["index"]] = epoch
        while len(self._count_epochs) > 64:
            self._count_epochs.popitem(last=False)
        return True

    def _agg_field(self, idx, call, want_int):
        field_name = call.args.get("field") or call.args.get("_field") \
            or call.field_arg()
        field = idx.field(field_name) if field_name else None
        if field is None:
            return None
        if want_int != (field.options.type == "int"):
            return None
        return field

    def _try_sum(self, idx, call, shards):
        """Sum(filter?, field=f) merged over the global mesh: the BSI
        bit planes form [depth, shards, words] globally-sharded arrays and
        the per-plane popcounts all-reduce over the fabric. Returns the
        final ValCount with the field base applied (field.go:1583),
        or None to fall back."""
        from ..exec.result import ValCount

        field = self._agg_field(idx, call, want_int=True)
        if field is None:
            return None
        filter_call = call.children[0] if call.children else None
        step = self._gate(idx, shards)
        step["kind"] = "sum"
        step["field"] = field.name
        if not self._plan_filter(idx, step, filter_call):
            return None
        resps = self._validate_on_peers(step)
        if resps is None:
            return None
        # depth can differ per node (it grows with out-of-range writes);
        # the step uses the cluster-wide max, peers zero-extend
        step["depth"] = max(
            [field.options.bit_depth]
            + [int(r.get("bit_depth", 0)) for r in resps])
        total, count = self._execute_step(step)
        return ValCount(total + field.options.base * count, count)

    def _try_minmax(self, idx, call, shards):
        """Min/Max over globally-sharded BSI planes: the narrowing
        bit-plane walk (ops.bsi min/max_unsigned) runs ONCE over the
        global [depth, shards, words] arrays — its any() reductions become
        cross-process collectives, so the global extremum and its count
        come out replicated (reference merge: ValCount.Smaller/Larger over
        per-node partials, executor.go:380-474)."""
        from ..exec.result import ValCount

        field = self._agg_field(idx, call, want_int=True)
        if field is None:
            return None
        filter_call = call.children[0] if call.children else None
        step = self._gate(idx, shards)
        step["kind"] = "minmax"
        step["field"] = field.name
        step["is_max"] = call.name == "Max"
        if not self._plan_filter(idx, step, filter_call):
            return None
        resps = self._validate_on_peers(step)
        if resps is None:
            return None
        step["depth"] = max(
            [field.options.bit_depth]
            + [int(r.get("bit_depth", 0)) for r in resps])
        empty, use_neg, bits, count = self._execute_step(step)
        if empty:
            return ValCount()
        mag = sum(int(b) << i for i, b in enumerate(bits))
        if use_neg:
            mag = -mag
        return ValCount(mag + field.options.base, count)

    #: candidate-row cap for SPMD TopN: [rows, shards, words] blocks must
    #: stay bounded per process; larger candidate sets fall back to HTTP
    TOPN_MAX_ROWS = 4096

    def _try_topn(self, idx, call, shards):
        """TopN merged over the global mesh: candidate rows are unioned
        across nodes in the validation round, then one [rows, shards,
        words] globally-sharded stack counts every candidate with the
        cross-process all-reduce. Returns the final trimmed pair list
        (reference merge: Pairs.Add cache.go:356 + executor.go:925), or
        None to fall back (attr filters / tanimoto / oversized candidate
        sets use the HTTP path)."""
        field_name = call.args.get("_field") or call.field_arg()
        field = idx.field(field_name) if field_name else None
        if field is None or field.options.type == "int":
            return None
        # tanimoto needs per-row plain counts + src count; attr filters
        # need the attr store; ids restricts the candidate set to exactly
        # the requested rows (restrict_ids semantics, executor.go:947) —
        # all stay on the HTTP/local path
        if call.args.get("tanimotoThreshold") \
                or call.args.get("attrName") is not None \
                or call.args.get("ids") is not None:
            return None
        if len(call.children) > 1:
            return None
        filter_call = call.children[0] if call.children else None
        step = self._gate(idx, shards)
        step["kind"] = "topn"
        step["field"] = field.name
        if not self._plan_filter(idx, step, filter_call):
            return None
        resps = self._validate_on_peers(step)
        if resps is None:
            return None
        # global candidate set = union of every node's cache/row ids
        rows = set(self._topn_candidates(idx, field.name))
        for r in resps:
            rows.update(int(x) for x in r.get("rows", []))
        rows = sorted(rows)
        if not rows:
            return []
        if len(rows) > self.TOPN_MAX_ROWS:
            # NOT silent (VERDICT r3 weak#4): a wide field crossing this
            # cliff shifts the query to the HTTP merge path.
            self.fallbacks += 1
            self.logger.printf(
                "spmd: TopN(%s) candidate set %d exceeds cap %d; "
                "falling back to HTTP merge", field.name, len(rows),
                self.TOPN_MAX_ROWS)
            return None
        step["rows"] = rows
        counts = self._execute_step(step)

        from ..exec.result import Pair

        threshold = max(int(call.args.get("threshold") or 1), 1)
        pairs = [Pair(r, c) for r, c in zip(rows, counts)
                 if c >= threshold]
        pairs.sort(key=lambda p: (-p.count, p.id))
        n = call.args.get("n")
        if n is not None:
            pairs = pairs[:int(n)]
        return pairs

    #: group-cell cap for SPMD GroupBy: the counting stack gathers
    #: [cells, shards, words] blocks — same budget shape as TopN rows
    GROUPBY_MAX_CELLS = 4096

    def _try_groupby(self, idx, call, shards):
        """GroupBy merged over the global mesh: per-child candidate rows
        union across nodes in the validation round, then ONE jitted
        program counts the full row cross-product with the cross-process
        all-reduce (reference merge: mergeGroupCounts over per-node
        partials, executor.go:1098-1237). Falls back on time fields,
        column/range-scoped Rows children, uncoverable filters, or
        oversized cross-products."""
        from ..core.field import FIELD_TYPE_INT, FIELD_TYPE_TIME
        from ..exec.result import FieldRow, GroupCount

        if not call.children:
            return None
        fields = []
        for child in call.children:
            if child.name != "Rows":
                return None
            if "column" in child.args or "from" in child.args \
                    or "to" in child.args:
                return None  # shard/time-scoped Rows: HTTP path
            fname = child.args.get("_field") or child.args.get("field") \
                or child.field_arg()
            field = idx.field(fname) if fname else None
            if field is None or field.type in (FIELD_TYPE_INT,
                                               FIELD_TYPE_TIME):
                return None
            fields.append(field)
        # Call-level `previous` list cursor (one row id per child), same
        # validation + seeding as the local executor. Validated BEFORE
        # the collective round: a malformed cursor must not cost a mesh
        # step just to fall back to HTTP and raise the same error there.
        from ..exec.executor import groupby_previous

        previous = groupby_previous(call, len(call.children))
        prev_t = tuple(previous) if previous is not None else None
        filter_call = call.args.get("filter")
        step = self._gate(idx, shards)
        step["kind"] = "groupby"
        step["fields"] = [f.name for f in fields]
        if not self._plan_filter(idx, step, filter_call):
            return None
        resps = self._validate_on_peers(step)
        if resps is None:
            return None
        child_rows = []
        for i, (child, field) in enumerate(zip(call.children, fields)):
            rows = set(self._rows_candidates(idx, field.name))
            for r in resps:
                per_child = r.get("rows", [])
                if i < len(per_child):
                    rows.update(int(x) for x in per_child[i])
            # Over-cap decline happens BEFORE previous/limit pruning: the
            # per-node candidate lists are truncated at the cap, so a
            # merged set past it may be missing rows — pruning first could
            # shrink an incomplete set under the cap and return a silently
            # wrong (partial) result instead of falling back to HTTP.
            if len(rows) > self.GROUPBY_MAX_CELLS:
                self.fallbacks += 1
                self.logger.printf(
                    "spmd: GroupBy child %s has %d candidate rows "
                    "(cap %d); falling back to HTTP merge", field.name,
                    len(rows), self.GROUPBY_MAX_CELLS)
                return None
            rows = sorted(rows)
            # child Rows() args apply to the GLOBAL merged set (exactly
            # executor._exec_rows semantics)
            previous = child.args.get("previous")
            if previous is not None:
                rows = [r for r in rows if r > int(previous)]
            limit = child.args.get("limit")
            if limit is not None:
                rows = rows[:int(limit)]
            child_rows.append(rows)
        # Seed the outermost child from the cursor (its iterator never
        # wraps); groups at or before the cursor are dropped
        # lexicographically below.
        if previous is not None:
            lo = previous[0] + (1 if len(child_rows) == 1 else 0)
            child_rows[0] = [r for r in child_rows[0] if r >= lo]
        cells = 1
        for rows in child_rows:
            cells *= len(rows)
        if cells == 0:
            return []
        if cells > self.GROUPBY_MAX_CELLS:
            self.fallbacks += 1
            self.logger.printf(
                "spmd: GroupBy cross-product %d cells exceeds cap %d; "
                "falling back to HTTP merge", cells,
                self.GROUPBY_MAX_CELLS)
            return None
        step["rows"] = child_rows
        counts = self._execute_step(step)

        # cell order == itertools.product order == lexicographic by row-id
        # tuple (child_rows are sorted), so the output is already in the
        # local executor's sorted-group order — no re-sort needed
        out = []
        for group, cnt in zip(itertools.product(*child_rows), counts):
            if cnt > 0 and (prev_t is None or group > prev_t):
                out.append(GroupCount(
                    [FieldRow(f.name, rid)
                     for f, rid in zip(fields, group)], cnt))
        limit = call.args.get("limit")
        if limit is not None:
            out = out[:int(limit)]
        # offset after the limit-bounded merge, no-op when past the end
        # (reference parity: executeGroupBy executor.go:1134-1143)
        offset = call.args.get("offset")
        if offset is not None and int(offset) < len(out):
            out = out[int(offset):]
        return out

    def _topn_candidates(self, idx, field_name):
        """This node's TopN candidate rows (shared policy:
        exec.executor.fragment_topn_candidates), capped at
        TOPN_MAX_ROWS+1: a single node already past the cap forces the
        HTTP fallback regardless of the union, so shipping more ids in
        the validate response would be pure wasted payload."""
        from ..exec.executor import fragment_topn_candidates

        field = idx.field(field_name)
        view = field.view(VIEW_STANDARD) if field is not None else None
        if view is None:
            return []
        rows = set()
        for frag in list(view.fragments.values()):
            rows.update(fragment_topn_candidates(frag))
        return sorted(rows)[:self.TOPN_MAX_ROWS + 1]

    def _rows_candidates(self, idx, field_name):
        """This node's present rows of a field (GroupBy child candidates;
        reference: fragment.rows via executeRowsShard executor.go:1319).
        Capped at GROUPBY_MAX_CELLS+1 — one over-cap child pushes the
        cross-product over the cell cap by itself (unless another child is
        empty, in which case the product is 0 either way), so the decline
        decision is preserved while the validate payload stays bounded."""
        field = idx.field(field_name)
        view = field.view(VIEW_STANDARD) if field is not None else None
        if view is None:
            return []
        rows = set()
        for frag in list(view.fragments.values()):
            rows.update(frag.row_ids())
        return sorted(rows)[:self.GROUPBY_MAX_CELLS + 1]

    def _validate_on_peers(self, step):
        """Pre-flight every peer; returns the list of OK responses, or
        None when any peer declined/was unreachable."""
        self.validations += 1
        resps = []

        def probe(node):
            try:
                client = self.client_factory(node.uri)
                client.timeout = self.VALIDATE_TIMEOUT
                resps.append(client.spmd_validate(step))
            except Exception:
                resps.append({"ok": False})

        threads = [threading.Thread(target=probe, args=(n,))
                   for n in self.cluster.peers()]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if len(resps) != len(self.cluster.peers()) \
                or not all(r.get("ok") for r in resps):
            return None
        return resps

    def validate(self, step):
        """Peer-side pre-flight check (POST /internal/spmd/validate).
        Static-compatibility checks only — the step carries its whole
        plan, so there is nothing tree-shaped to re-derive here. Aggregate
        kinds also contribute per-node data the coordinator merges:
        bit_depth for sum/minmax (depth grows locally past the declared
        range, field.set_value), candidate rows for topn/groupby."""
        idx = self.holder.index(step["index"])
        if idx is None:
            return {"ok": False, "reason": "index not found"}
        if int(step["dev_pp"]) != self._local_device_count():
            return {"ok": False, "reason": "device count mismatch"}
        if tuple(step.get("nodes", ())) != self._boot_node_ids:
            return {"ok": False, "reason": "membership mismatch"}
        out = {"ok": True}
        kind = step.get("kind", "count")
        if kind in ("sum", "minmax"):
            field = idx.field(step["field"])
            if field is None or field.options.type != "int":
                return {"ok": False, "reason": "not an int field"}
            out["bit_depth"] = field.options.bit_depth
        elif kind == "topn":
            field = idx.field(step["field"])
            if field is None or field.options.type == "int":
                return {"ok": False, "reason": "not a set field"}
            # contribute this node's candidate rows to the global union
            out["rows"] = self._topn_candidates(idx, step["field"])
        elif kind == "groupby":
            out["rows"] = [self._rows_candidates(idx, f)
                           for f in step["fields"]]
        return out

    # -- step execution (every process) --------------------------------------

    def run_step(self, step):
        """HTTP-handler entry for peer processes (blocking legacy
        announcements, serve_mode != on)."""
        # observatory t0: overwrite unconditionally — any coordinator
        # stamp that leaked over the wire is from a different process's
        # perf_counter and meaningless here; announce_recv then measures
        # this node's step-lock wait
        step["_recv_t"] = time.perf_counter()
        with self._lock:
            return self._enter_exit_run(step)

    def run_stream(self, step):
        """HTTP-handler entry for STREAMED announcements (serve == on):
        enqueue by sequence number and ack immediately — the stream
        runner thread executes steps in seq order, so the coordinator's
        announcing thread never blocks on this peer's collective."""
        seq = int(step["seq"])
        # observatory t0 at ENQUEUE: announce_recv then measures the
        # stream-queue wait + step-lock wait (pipeline occupancy per step)
        step["_recv_t"] = time.perf_counter()
        with self._stream_cond:
            self._stream_queue[seq] = step
            if self._stream_next is None:
                self._stream_next = seq
            if self._stream_thread is None \
                    or not self._stream_thread.is_alive():
                self._stream_thread = threading.Thread(
                    target=self._stream_loop, name="spmd-stream",
                    daemon=True)
                self._stream_thread.start()
            self._stream_cond.notify_all()
        return {"ok": True, "seq": seq, "queued": len(self._stream_queue)}

    def close(self):
        """Stop the stream runner (server shutdown)."""
        with self._stream_cond:
            self._stream_closed = True
            self._stream_cond.notify_all()

    def _stream_loop(self):
        """Peer-side stream runner: executes queued steps strictly in
        sequence order. A gap (announcement lost while later steps keep
        arriving) times out after STREAM_GAP_TIMEOUT and resyncs to the
        lowest queued seq — the coordinator's collective for the lost
        step already failed via the distributed-runtime timeout and fell
        back to HTTP, so skipping it here preserves the identical
        program order on every process for the steps that DID run."""
        from ..utils import flightrec, incident

        while True:
            with self._stream_cond:
                deadline = None
                gap_started = None
                while not self._stream_closed:
                    nxt = self._stream_next
                    if nxt is not None and nxt in self._stream_queue:
                        break
                    if self._stream_queue:
                        now = time.monotonic()
                        if deadline is None:
                            # gap ONSET: later steps queued but the
                            # expected seq is missing. Announce it NOW —
                            # a silent STREAM_GAP_TIMEOUT stall was
                            # previously invisible until the resync —
                            # and trigger the collective_stall autopsy
                            # so every peer's step ring is captured
                            # while the gap is still open.
                            deadline = now + self.STREAM_GAP_TIMEOUT
                            gap_started = now
                            self.gap_onsets += 1
                            flightrec.record(
                                "spmd.stream_gap", expected=nxt,
                                queued=len(self._stream_queue),
                                timeout_seconds=self.STREAM_GAP_TIMEOUT)
                            incident.maybe_trigger(
                                "collective_stall", cause="stream_gap",
                                expected_seq=nxt if nxt is not None
                                else -1,
                                queued=len(self._stream_queue))
                        if now >= deadline:
                            resync = min(self._stream_queue)
                            self.stream_resyncs += 1
                            self.gap_stall_seconds += now - gap_started
                            gap_started = None
                            flightrec.record(
                                "spmd.stream_resync",
                                expected=nxt, resync=resync)
                            self.logger.printf(
                                "spmd: stream gap at seq %s; resyncing "
                                "to %s", nxt, resync)
                            self._stream_next = resync
                            break
                        self._stream_cond.wait(deadline - now)
                    else:
                        deadline = None
                        gap_started = None
                        self._stream_cond.wait(1.0)
                if gap_started is not None:
                    # gap closed by arrival (or shutdown): account the
                    # stall time the pipeline spent blocked on it
                    self.gap_stall_seconds += time.monotonic() \
                        - gap_started
                if self._stream_closed:
                    return
                step = self._stream_queue.pop(self._stream_next)
                self._stream_next += 1
            try:
                with self._lock:
                    # result discarded: the collective output is
                    # replicated, only the coordinator reads it
                    self._enter_exit_run(step)
            except Exception as e:
                # the coordinator saw the same collective failure and
                # fell back; keep this runner alive for the next step
                self.stream_errors += 1
                self.logger.printf(
                    "spmd: streamed step %s failed on this node: %s",
                    step.get("seq"), e)

    def _run_step_locked(self, step):
        # A validated peer MUST enter the collective: every failure mode
        # past this point (index/field dropped by a racing DDL, fragment
        # churn) degrades to zero planes inside _local_block — never an
        # exception that would leave the other processes blocked in the
        # rendezvous (the ADVICE r3 wedge). steps_run increments are under
        # self._lock (held here by both entry paths).
        idx = self.holder.index(step["index"])
        kind = step.get("kind", "count")
        if kind == "count":
            return self._run_count_step(idx, step)
        if kind == "count_batch":
            return self._run_count_batch_step(idx, step)
        if kind == "sum":
            return self._run_sum_step(idx, step)
        if kind == "minmax":
            return self._run_minmax_step(idx, step)
        if kind == "topn":
            return self._run_topn_step(idx, step)
        if kind == "groupby":
            return self._run_groupby_step(idx, step)
        raise SpmdError(f"unknown spmd step kind: {kind}")

    def _local_block(self, idx, step, field_name, row_id,
                     view_name=None):
        """This process's [seg_len, W] block of one row over its owned
        shards. DEFENSIVE by design: zero planes for shards, fragments,
        fields, views — or a whole index — this process doesn't hold
        (including anything lost to a racing DDL after validation); zeros
        are count-neutral for every covered op, and a throw here would
        wedge the collective (see _run_step_locked)."""
        seg_len = int(step["seg_len"])
        my_shards = step["segments"].get(self.cluster.local_id, [])
        if len(my_shards) > seg_len:
            # cannot happen with a correct coordinator (seg_len is the
            # padded max segment); truncate loudly rather than wedge the
            # rendezvous by raising
            self.logger.printf(
                "spmd: segment length %d exceeds seg_len %d on step %s; "
                "truncating", len(my_shards), seg_len, step.get("step"))
            my_shards = my_shards[:seg_len]
        local = np.zeros((seg_len, WORDS_PER_ROW), dtype=np.uint32)
        try:
            field = idx.field(field_name) if idx is not None else None
            view = field.view(view_name or VIEW_STANDARD) \
                if field is not None else None
            if view is not None:
                for j, shard in enumerate(my_shards):
                    frag = view.fragment(shard)
                    if frag is not None:
                        plane = frag.row_plane(row_id)
                        if plane is not None:
                            local[j] = np.asarray(plane)
        except Exception as e:
            self.logger.printf(
                "spmd: local block gather failed (%s row %s): %s — "
                "contributing zero planes", field_name, row_id, e)
        return local

    def _local_cond_block(self, idx, step, field_name, op, vals):
        """This process's [seg_len, W] block of one BSI condition leaf
        (e.g. v > 10): evaluated per owned shard against LOCAL planes with
        the shared condition plan — per-node clamping against local bit
        depth is exact for local data, since a node's values were written
        within its own depth. Defensive like _local_block."""
        from ..exec.bsicond import condition_from_key

        seg_len = int(step["seg_len"])
        my_shards = step["segments"].get(self.cluster.local_id, [])
        local = np.zeros((seg_len, WORDS_PER_ROW), dtype=np.uint32)
        try:
            call = Call("Row", args={
                field_name: condition_from_key(op, vals)})
            ex = self._local_executor()
            for j, shard in enumerate(my_shards[:seg_len]):
                plane = ex.bitmap_call_shard(idx, call, shard)
                if plane is not None:
                    local[j] = np.asarray(plane)
        except Exception as e:
            self.logger.printf(
                "spmd: local condition gather failed (%s %s %s): %s — "
                "contributing zero planes", field_name, op, vals, e)
        return local

    def _local_executor(self):
        """Executor for per-shard condition-leaf evaluation. The API
        shares its serving executor here (server/api.py) so no second
        evaluator is built; standalone/test construction falls back to a
        lazy private instance."""
        if self._local_exec is None:
            from ..exec.executor import Executor

            self._local_exec = Executor(self.holder)
        return self._local_exec

    def _local_leaf_block(self, idx, step, entry):
        """This process's [seg_len, W] host block for one wire leaf
        (defensive: zeros for anything missing locally)."""
        if entry[0] == "bsicond":
            _, field_name, op, vals = entry
            return self._local_cond_block(idx, step, field_name, op, vals)
        if entry[0] == "timerow":
            # union across the quantum-view cover, host-side (each
            # view's block is defensive zeros when absent locally)
            _, field_name, row_id, views = entry
            local = np.zeros((int(step["seg_len"]), WORDS_PER_ROW),
                             dtype=np.uint32)
            for view_name in views:
                local |= self._local_block(
                    idx, step, field_name, int(row_id),
                    view_name=view_name)
            return local
        _, field_name, row_id = entry
        return self._local_block(idx, step, field_name, int(row_id))

    def _leaf_array(self, idx, step, entry, sharding, global_shape):
        """ONE globally-sharded leaf array, mesh-cache aware.

        serve == on: probe the mesh-resident cache first — a hit returns
        the device-placed global-array handle without touching host
        fragments or re-uploading (the tentpole win). Per-process cache
        divergence is safe: this handle only feeds this process's
        addressable shards (meshstacks module doc).
        serve == shadow: legacy gather serves; the fresh block feeds the
        cache's divergence detector.
        serve == off/http: byte-identical legacy path, cache untouched.
        """
        import jax

        from .meshstacks import entry_key

        seg_len = int(step["seg_len"])
        my_shards = tuple(step["segments"].get(self.cluster.local_id, []))
        key = (step["index"], entry_key(entry), seg_len, my_shards)
        gens = None
        if self.serve_mode in ("on", "shadow"):
            gens = self.mesh_cache.gens(idx, entry, my_shards)
        if self.serve_mode == "on" and gens is not None:
            arr = self.mesh_cache.get(key, gens)
            if arr is not None:
                return arr
        local = self._local_leaf_block(idx, step, entry)
        arr = jax.make_array_from_process_local_data(
            sharding, local, global_shape=global_shape)
        if gens is not None:
            if self.serve_mode == "on":
                self.mesh_cache.put(key, gens, arr, local)
            else:
                self.mesh_cache.shadow_probe(key, gens, local)
        return arr

    def _leaf_arrays(self, idx, step):
        """Globally-sharded [S, W] arrays for a step's plan leaves
        (tagged wire entries: ["row", f, r] | ["bsicond", f, op, vals] |
        ["timerow", f, r, views])."""
        n_proc = self._num_processes()
        seg_len = int(step["seg_len"])
        sharding = self._global_sharding()
        global_shape = (n_proc * seg_len, WORDS_PER_ROW)
        arrays = [self._leaf_array(idx, step, entry, sharding,
                                   global_shape)
                  for entry in step.get("leaves", [])]
        return arrays, global_shape

    def _run_count_step(self, idx, step):
        import jax

        from ..ops.bitplane import combine_hi_lo

        sig = sig_from_wire(step["sig"])
        arrays, _ = self._leaf_arrays(idx, step)
        self._mark_phase("stack_gather")
        fn = self._count_fn(sig, len(arrays))
        out = fn(*arrays)
        self._mark_phase("device_enter")  # compile lands here (cold key)
        jax.block_until_ready(out)
        self._mark_phase("psum")
        self.steps_run += 1
        hi, lo = out
        result = int(combine_hi_lo(hi, lo))
        self._mark_phase("result_fetch")
        return result

    def _run_count_batch_step(self, idx, step):
        """K Count plans in ONE collective step: gather every plan's
        leaf arrays (the mesh cache dedups the bucket-padding repeats and
        shared leaves across plans), evaluate all trees in one jitted
        program — same-signature plans vmapped over a stacked leaf axis —
        and all-reduce all K per-shard popcounts together. One
        announcement, one program, one psum for the whole batch."""
        import jax

        from ..ops.bitplane import combine_hi_lo

        sigs = []
        arities = []
        all_arrays = []
        for plan in step["plans"]:
            sigs.append(sig_from_wire(plan["sig"]))
            sub = dict(step)
            sub["leaves"] = plan["leaves"]
            arrays, _ = self._leaf_arrays(idx, sub)
            arities.append(len(arrays))
            all_arrays.extend(arrays)
        self._mark_phase("stack_gather")
        fn = self._count_batch_fn(tuple(sigs), tuple(arities))
        out = fn(*all_arrays)
        self._mark_phase("device_enter")
        jax.block_until_ready(out)
        self._mark_phase("psum")
        self.steps_run += 1
        self.batch_steps += 1
        hilo = np.asarray(out)  # [2, K]: one host transfer
        result = [int(combine_hi_lo(int(h), int(l)))
                  for h, l in zip(hilo[0], hilo[1])]
        self._mark_phase("result_fetch")
        return result

    def _bsi_arrays(self, idx, step):
        """Globally-sharded (planes [D,S,W], sign [S,W], exists [S,W]) for
        a sum/minmax step. Zero-extension to the cluster-wide max depth is
        exact: absent magnitude planes contribute 0 to every popcount.
        A write racing this step can grow the local bit_depth past the
        validated step depth; the racing value's planes above step depth
        are simply not read this query — an ordinary read/write race
        outcome, not corruption."""
        import jax

        from ..core.fragment import (
            BSI_EXISTS_BIT,
            BSI_OFFSET_BIT,
            BSI_SIGN_BIT,
        )

        # at least one magnitude plane so the [D,S,W] stack is never empty
        # (an all-zero plane is exact: it adds 0 to every popcount)
        depth = max(1, int(step["depth"]))
        bsi_view = VIEW_BSI_GROUP_PREFIX + step["field"]
        n_proc = self._num_processes()
        seg_len = int(step["seg_len"])
        plane_sh = self._global_sharding(shard_axis=1, ndim=3)
        row_sh = self._global_sharding()
        row_shape = (n_proc * seg_len, WORDS_PER_ROW)

        local_planes = np.stack([
            self._local_block(idx, step, step["field"],
                              BSI_OFFSET_BIT + i, view_name=bsi_view)
            for i in range(depth)])
        planes = jax.make_array_from_process_local_data(
            plane_sh, local_planes,
            global_shape=(depth,) + row_shape)
        sign = jax.make_array_from_process_local_data(
            row_sh, self._local_block(idx, step, step["field"],
                                      BSI_SIGN_BIT, view_name=bsi_view),
            global_shape=row_shape)
        exists = jax.make_array_from_process_local_data(
            row_sh, self._local_block(idx, step, step["field"],
                                      BSI_EXISTS_BIT, view_name=bsi_view),
            global_shape=row_shape)
        return planes, sign, exists

    def _run_sum_step(self, idx, step):
        """BSI Sum over globally-sharded bit planes (reference per-shard
        algorithm: fragment.sum fragment.go:1068; the cross-node merge is
        the all-reduce XLA inserts over the [*, shards, words] arrays)."""
        import jax

        from ..ops.bitplane import combine_hi_lo

        depth = int(step["depth"])
        planes, sign, exists = self._bsi_arrays(idx, step)
        sig = sig_from_wire(step["sig"])
        stacks, _ = self._leaf_arrays(idx, step)
        self._mark_phase("stack_gather")

        fn = self._sum_fn(sig, len(stacks))
        out = fn(planes, sign, exists, *stacks)
        self._mark_phase("device_enter")
        jax.block_until_ready(out)
        self._mark_phase("psum")
        res = [np.asarray(r) for r in out]
        p_hi, p_lo, n_hi, n_lo, c_hi, c_lo = res
        total = 0
        for i in range(depth):
            total += combine_hi_lo(p_hi[i], p_lo[i]) << i
            total -= combine_hi_lo(n_hi[i], n_lo[i]) << i
        self.steps_run += 1
        result = total, int(combine_hi_lo(c_hi, c_lo))
        self._mark_phase("result_fetch")
        return result

    def _run_minmax_step(self, idx, step):
        """Min/Max narrowing walk over globally-sharded planes; the
        replicated outputs (empty, use_neg, bits, count) decode on the
        coordinator (reference sign rules: fragment.go:1110-1227)."""
        import jax

        from ..ops.bitplane import combine_hi_lo

        planes, sign, exists = self._bsi_arrays(idx, step)
        sig = sig_from_wire(step["sig"])
        stacks, _ = self._leaf_arrays(idx, step)
        self._mark_phase("stack_gather")

        fn = self._minmax_fn(sig, len(stacks), bool(step["is_max"]))
        out = fn(planes, sign, exists, *stacks)
        self._mark_phase("device_enter")
        jax.block_until_ready(out)
        self._mark_phase("psum")
        empty, use_neg, bits, c_hi, c_lo = out
        self.steps_run += 1
        result = (bool(empty), bool(use_neg),
                  [int(b) for b in np.asarray(bits)],
                  int(combine_hi_lo(c_hi, c_lo)))
        self._mark_phase("result_fetch")
        return result

    def _run_topn_step(self, idx, step):
        """Candidate-row counts over a globally-sharded [rows, shards,
        words] stack (reference per-shard scan: fragment.top
        fragment.go:1570; the heap merge becomes the all-reduce)."""
        import jax

        from ..ops.bitplane import combine_hi_lo

        rows = [int(r) for r in step["rows"]]
        n_proc = self._num_processes()
        seg_len = int(step["seg_len"])
        rows_sh = self._global_sharding(shard_axis=1, ndim=3)
        row_shape = (n_proc * seg_len, WORDS_PER_ROW)

        local = np.stack([
            self._local_block(idx, step, step["field"], r) for r in rows])
        stack = jax.make_array_from_process_local_data(
            rows_sh, local, global_shape=(len(rows),) + row_shape)

        sig = sig_from_wire(step["sig"])
        stacks, _ = self._leaf_arrays(idx, step)
        self._mark_phase("stack_gather")

        fn = self._topn_fn(sig, len(stacks))
        out = fn(stack, *stacks)
        self._mark_phase("device_enter")
        jax.block_until_ready(out)
        self._mark_phase("psum")
        hi, lo = out
        self.steps_run += 1
        totals = combine_hi_lo(hi, lo)
        result = [int(t) for t in totals]
        self._mark_phase("result_fetch")
        return result

    def _run_groupby_step(self, idx, step):
        """Cross-product counts over per-field globally-sharded [rows,
        shards, words] stacks: ONE jitted program gathers each cell's row
        combination, intersects, popcounts, and all-reduces across
        processes (reference per-(shard×cell) scan: executeGroupByShard
        executor.go:1238)."""
        import jax

        from ..ops.bitplane import combine_hi_lo

        n_proc = self._num_processes()
        seg_len = int(step["seg_len"])
        rows_sh = self._global_sharding(shard_axis=1, ndim=3)
        row_shape = (n_proc * seg_len, WORDS_PER_ROW)

        field_stacks = []
        lens = []
        for field_name, rows in zip(step["fields"], step["rows"]):
            rows = [int(r) for r in rows]
            lens.append(len(rows))
            local = np.stack([
                self._local_block(idx, step, field_name, r) for r in rows])
            field_stacks.append(jax.make_array_from_process_local_data(
                rows_sh, local, global_shape=(len(rows),) + row_shape))

        sig = sig_from_wire(step["sig"])
        stacks, _ = self._leaf_arrays(idx, step)
        self._mark_phase("stack_gather")

        fn = self._groupby_fn(tuple(lens), sig, len(stacks))
        out = fn(*field_stacks, *stacks)
        self._mark_phase("device_enter")
        jax.block_until_ready(out)
        self._mark_phase("psum")
        hi, lo = out
        self.steps_run += 1
        totals = combine_hi_lo(hi, lo)
        result = [int(t) for t in totals]
        self._mark_phase("result_fetch")
        return result

    # -- compiled programs ----------------------------------------------------

    def _get_fn(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = build()
            self._fns[key] = fn
            while len(self._fns) > self.MAX_FNS:
                self._fns.popitem(last=False)
        else:
            self._fns.move_to_end(key)
        return fn

    def _count_fn(self, sig, arity):
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import StackedEvaluator
        from ..ops.bitplane import hi_lo

        def build():
            @jax.jit
            def fn(*stacks):
                acc = StackedEvaluator._tree_eval(sig, stacks)
                per_shard = jnp.sum(
                    jax.lax.population_count(acc).astype(jnp.int32),
                    axis=-1)
                return hi_lo(per_shard)

            return fn

        return self._get_fn(("count", sig, arity), build)

    def _count_batch_fn(self, sigs, arities):
        """K Count trees in one program. Runs of IDENTICAL (sig, arity)
        — the common case after bucket padding repeats plans[0] — are
        stacked on a new leading axis and evaluated with ONE vmapped
        tree walk; distinct signatures evaluate inline in the same trace.
        Either way XLA sees a single program and inserts ONE
        cross-process reduce for all K outputs. Returns a single
        stacked [2, K] array — row 0 the hi halves, row 1 the lo
        halves, in plan order — so the warm path costs one reduce pair
        and one host fetch total."""
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import tree_eval
        from ..ops.bitplane import hi_lo

        def build():
            # group plan positions by identical (sig, arity) runs
            groups = OrderedDict()
            for pos, sa in enumerate(zip(sigs, arities)):
                groups.setdefault(sa, []).append(pos)
            offsets = []
            off = 0
            for a in arities:
                offsets.append(off)
                off += a

            @jax.jit
            def fn(*stacks):
                def count(sig, leaf_stacks):
                    acc = tree_eval(sig, leaf_stacks)
                    return jnp.sum(
                        jax.lax.population_count(acc).astype(jnp.int32),
                        axis=-1)

                per_plan = [None] * len(sigs)
                for (sig, arity), positions in groups.items():
                    if len(positions) > 1 and arity > 0:
                        # [G, S, W] per leaf slot -> one vmapped walk
                        batched = [
                            jnp.stack([stacks[offsets[p] + i]
                                       for p in positions])
                            for i in range(arity)]
                        per_shard = jax.vmap(
                            lambda *ls, _sig=sig: count(_sig, ls))(
                                *batched)
                        for g, p in enumerate(positions):
                            per_plan[p] = per_shard[g]
                    else:
                        for p in positions:
                            ls = stacks[offsets[p]:offsets[p] + arity]
                            per_plan[p] = count(sig, ls)
                # ONE reduce + ONE fetch for the whole batch: per-plan
                # hi_lo in a Python loop would emit 2K separate
                # cross-process all-reduces (each pays a full gloo
                # sync); stacking the [S] per-shard counts to [K, S]
                # first makes the hi/lo sums a single pair of
                # collectives regardless of K, and stacking hi over lo
                # makes the host transfer a single [2, K] array
                return jnp.stack(hi_lo(jnp.stack(per_plan), axis=-1))

            return fn

        return self._get_fn(("count_batch", sigs, arities), build)

    def _sum_fn(self, sig, arity):
        """(planes [D,S,W], sign, exists, *filter leaves) -> per-plane
        pos/neg popcounts + consider count as (hi, lo) int32 pairs, with
        XLA inserting the cross-process reduce."""
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import StackedEvaluator
        from ..ops.bitplane import hi_lo

        def build():
            @jax.jit
            def fn(planes, sign, exists, *stacks):
                consider = exists
                if sig is not None:
                    consider = consider & StackedEvaluator._tree_eval(
                        sig, stacks)
                pos = consider & ~sign
                neg = consider & sign
                pc = jnp.sum(jax.lax.population_count(
                    planes & pos[None]).astype(jnp.int32), axis=-1)
                nc = jnp.sum(jax.lax.population_count(
                    planes & neg[None]).astype(jnp.int32), axis=-1)
                cc = jnp.sum(jax.lax.population_count(
                    consider).astype(jnp.int32), axis=-1)
                return (*hi_lo(pc, axis=-1), *hi_lo(nc, axis=-1),
                        *hi_lo(cc))

            return fn

        return self._get_fn(("sum", sig, arity), build)

    def _minmax_fn(self, sig, arity, is_max):
        """Global Min/Max in one program over globally-sharded planes —
        both sign-branch walks computed branchlessly, selected per the
        reference's rules (same kernel shape as the local stacked
        evaluator's _minmax_fn; its any() reductions become collectives
        here)."""
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import StackedEvaluator
        from ..ops import bsi as bsi_ops
        from ..ops.bitplane import hi_lo

        def build():
            @jax.jit
            def fn(planes, sign, exists, *stacks):
                consider = exists
                if sig is not None:
                    consider = consider & StackedEvaluator._tree_eval(
                        sig, stacks)
                pos = consider & ~sign
                neg = consider & sign
                has_pos = jnp.any(pos != 0)
                has_neg = jnp.any(neg != 0)
                empty = ~(has_pos | has_neg)
                if is_max:
                    b_pos, f_pos = bsi_ops.max_unsigned(planes, pos)
                    b_neg, f_neg = bsi_ops.min_unsigned(planes, neg)
                    use_neg = ~has_pos
                else:
                    b_neg, f_neg = bsi_ops.max_unsigned(planes, neg)
                    b_pos, f_pos = bsi_ops.min_unsigned(planes, pos)
                    use_neg = has_neg
                bits = jnp.where(use_neg, b_neg, b_pos)
                final = jnp.where(use_neg, f_neg, f_pos)
                per_shard = jnp.sum(
                    jax.lax.population_count(final).astype(jnp.int32),
                    axis=-1)
                return (empty, use_neg, bits, *hi_lo(per_shard))

            return fn

        return self._get_fn(("minmax", sig, arity, is_max), build)

    def _topn_fn(self, sig, arity):
        """(rows [R,S,W], *filter leaves) -> per-row (hi [R], lo [R])
        counts of row ∩ filter, all-reduced across processes."""
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import StackedEvaluator
        from ..ops.bitplane import hi_lo

        def build():
            @jax.jit
            def fn(stack, *stacks):
                x = stack
                if sig is not None:
                    filt = StackedEvaluator._tree_eval(sig, stacks)
                    x = x & filt[None]
                per_shard = jnp.sum(
                    jax.lax.population_count(x).astype(jnp.int32),
                    axis=-1)
                return hi_lo(per_shard, axis=-1)

            return fn

        return self._get_fn(("topn", sig, arity), build)

    def _groupby_fn(self, lens, sig, arity):
        """(field stacks [R_i,S,W]..., *filter leaves) -> per-cell
        (hi [C], lo [C]) counts of the full cross-product. The cell index
        arrays derive from `lens` alone INSIDE the trace (meshgrid of
        iotas), so every process compiles the identical program with no
        host-data divergence; cell order = itertools.product order
        (meshgrid indexing='ij')."""
        import jax
        import jax.numpy as jnp

        from ..exec.stacked import StackedEvaluator
        from ..ops.bitplane import hi_lo

        def build():
            @jax.jit
            def fn(*arrays):
                field_stacks = arrays[:len(lens)]
                stacks = arrays[len(lens):]
                grids = jnp.meshgrid(
                    *[jnp.arange(n) for n in lens], indexing="ij")
                idxs = [g.reshape(-1) for g in grids]
                x = field_stacks[0][idxs[0]]  # [C, S, W]
                for s, ix in zip(field_stacks[1:], idxs[1:]):
                    x = x & s[ix]
                if sig is not None:
                    filt = StackedEvaluator._tree_eval(sig, stacks)
                    x = x & filt[None]
                per_shard = jnp.sum(
                    jax.lax.population_count(x).astype(jnp.int32),
                    axis=-1)
                return hi_lo(per_shard, axis=-1)

            return fn

        return self._get_fn(("groupby", lens, sig, arity), build)

    def stats(self):
        return {"steps": self.steps_run,
                "initialized": type(self)._initialized,
                "serve_mode": self.serve_mode,
                "validations": self.validations,
                "validations_skipped": self.validations_skipped,
                "forwarded": self.forwarded,
                "forward_errors": self.forward_errors,
                "fallbacks": self.fallbacks,
                "batch_steps": self.batch_steps,
                "fused_steps": self.fused_steps,
                "fused_queries": self.fused_queries}

    def debug_snapshot(self):
        """GET /debug/spmd: serve mode + mesh shape, the step-lifecycle
        counters the wedge classifier reads (announced vs entered vs
        exited per node), stream state, mesh-cache stats, and the HTTP
        data-plane byte counter (zero while collectives serve)."""
        from ..server import client as client_mod

        with self._stream_cond:
            stream = {
                "next": self._stream_next,
                "queued": len(self._stream_queue),
                "errors": self.stream_errors,
                "resyncs": self.stream_resyncs,
            }
        try:
            mesh = self.mesh_shape()
        except Exception:  # backend not initialized yet
            mesh = None
        return {
            "serve_mode": self.serve_mode,
            "initialized": type(self)._initialized,
            "mesh": mesh,
            "steps": {
                "run": self.steps_run,
                "announced": self.steps_announced,
                "entered": self.steps_entered,
                "exited": self.steps_exited,
                "last_seq": self.last_seq,
                "batch": self.batch_steps,
                "fused": self.fused_steps,
            },
            "queries": {
                "fused": self.fused_queries,
                "forwarded": self.forwarded,
                "fallbacks": self.fallbacks,
            },
            "stream": stream,
            "stream_gap_timeout": self.STREAM_GAP_TIMEOUT,
            "observatory": self.observatory_stats(),
            "mesh_cache": self.mesh_cache.stats(),
            "http_data_plane_bytes": client_mod.data_plane_bytes(),
        }

    # -- mesh observatory (read side) -----------------------------------------

    def observatory_stats(self):
        """Compact observatory counters (no ring contents): per-phase
        totals, pipeline occupancy, gap + straggler tallies."""
        with self._obs_lock:
            totals = {p: {"count": c, "seconds": round(s, 6)}
                      for p, (c, s) in self._phase_totals.items()}
            ring = len(self._step_ring)
        return {
            "steps_recorded": ring,
            "ring_size": self.STEP_RING_SIZE,
            "phase_totals": totals,
            "occupancy": self.occupancy(),
            "straggler_flags": self.straggler_flags_total,
        }

    def occupancy(self):
        """Step-stream pipeline occupancy: queue depth, how far this
        node's execution lags the highest announced seq it has seen, and
        cumulative time the runner spent blocked on sequence gaps."""
        with self._stream_cond:
            queued = len(self._stream_queue)
            head = max(self._stream_queue) if self._stream_queue else None
            nxt = self._stream_next
        return {
            "queue_depth": queued,
            "seq_lag": max(0, (head or self.last_seq) - self.last_seq),
            "stream_next": nxt,
            "last_seq": self.last_seq,
            "gap_onsets": self.gap_onsets,
            "gap_stall_seconds": round(self.gap_stall_seconds, 6),
        }

    def register_gauges(self):
        """Scrape-time pipeline-occupancy gauges on the process-global
        stats client (called once from cli.cmd_server — NOT __init__, so
        short-lived test planes never leak gauge closures)."""
        from ..utils.stats import global_stats

        if not hasattr(global_stats, "gauge_fn"):
            return
        global_stats.gauge_fn(
            "spmd_stream_queue_depth",
            lambda: len(self._stream_queue))
        global_stats.gauge_fn(
            "spmd_stream_seq_lag",
            lambda: max(0, (max(self._stream_queue)
                            if self._stream_queue else self.last_seq)
                        - self.last_seq))
        global_stats.gauge_fn(
            "spmd_stream_gap_stall_seconds",
            lambda: self.gap_stall_seconds)

    def _local_node_id(self):
        if self.cluster is not None:
            return self.cluster.local_id
        return "local"

    def steps_local(self, seq=None, limit=None):
        """This node's slice of the step timeline (what the coordinator
        fans out for with ?local=true): recent step records with
        per-phase walls, stamped with this node's wall clock so the
        caller can skew-correct from the RPC envelope."""
        with self._obs_lock:
            steps = list(self._step_ring)
        if seq is not None:
            steps = [r for r in steps if r["seq"] == seq]
        elif limit is not None and limit > 0:
            steps = steps[-int(limit):]
        return {
            "node": self._local_node_id(),
            "time": time.time(),
            "steps": steps,
            "occupancy": self.occupancy(),
        }

    def steps_timeline(self, seq=None, limit=32, local_only=False):
        """GET /debug/spmd/steps[/{seq}]: the cross-node step timeline.

        Fans out to mesh peers for their local slices (?local=true, the
        PR-17 debug_trace pattern), estimates each peer's clock offset
        from the RPC envelope (envelope_skew — same symmetric-delay
        assumption as tracing.estimate_skew), shifts every peer's step
        starts onto this node's clock, and merges per-seq into one
        timeline with per-phase straggler attribution. Straggler flags
        are edge-triggered: each (seq, node, phase) counts toward
        spmd_step_straggler_total{node,phase} and fires the
        spmd.straggler flightrec event exactly once, no matter how often
        the timeline is scraped."""
        local_id = self._local_node_id()
        payloads = {local_id: (self.steps_local(seq=seq, limit=limit),
                               0.0)}
        if not local_only and self.cluster is not None \
                and len(self.cluster.nodes) > 1:
            from ..utils import tracing

            with tracing.with_span(None):  # debug plumbing: never trace
                for node in self.cluster.peers():
                    try:
                        client = self.client_factory(node.uri)
                        t_send = time.time()
                        remote = client.debug_spmd_steps(seq=seq,
                                                         limit=limit)
                        t_recv = time.time()
                    except Exception:  # best-effort: peer down/old
                        continue
                    if not remote or remote.get("steps") is None:
                        continue
                    theta = envelope_skew(
                        t_send, t_recv,
                        float(remote.get("time") or t_recv))
                    payloads[remote.get("node", node.id)] = (remote,
                                                             theta)
        merged = {}
        for node, (payload, theta) in payloads.items():
            for rec in payload.get("steps", []):
                s = merged.setdefault(rec["seq"], {
                    "seq": rec["seq"],
                    "kind": rec.get("kind", "count"),
                    "index": rec.get("index", ""),
                    "peers": {},
                })
                if rec.get("trace") and not s.get("trace"):
                    s["trace"] = rec["trace"]
                s["peers"][node] = {
                    # peer wall-clock start shifted onto OUR clock
                    "start": round(rec["start"] - theta, 6),
                    "wall_seconds": rec["wall_seconds"],
                    "phases": rec.get("phases", {}),
                    "ok": rec.get("ok", True),
                }
        steps = [merged[k] for k in sorted(merged)]
        for s in steps:
            s["stragglers"] = attribute_stragglers(
                {n: p["phases"] for n, p in s["peers"].items()},
                self.STRAGGLER_FACTOR, self.STRAGGLER_NOISE_FLOOR)
            self._flag_stragglers(s["seq"], s["stragglers"])
        return {
            "node": local_id,
            "skew_seconds": {n: round(th, 6)
                             for n, (_, th) in payloads.items()},
            "straggler_factor": self.STRAGGLER_FACTOR,
            "noise_floor_seconds": self.STRAGGLER_NOISE_FLOOR,
            "steps": steps,
        }

    def _flag_stragglers(self, seq, flags):
        """Edge-triggered straggler accounting (see steps_timeline)."""
        if not flags:
            return
        from ..utils import flightrec
        from ..utils.stats import global_stats

        for flag in flags:
            key = (seq, flag["node"], flag["phase"])
            with self._obs_lock:
                if key in self._straggler_flags:
                    continue
                self._straggler_flags[key] = 1
                while len(self._straggler_flags) \
                        > self.STRAGGLER_FLAGS_MAX:
                    self._straggler_flags.popitem(last=False)
                self.straggler_flags_total += 1
            try:
                global_stats.count(
                    "spmd_step_straggler_total",
                    tags={"node": str(flag["node"]),
                          "phase": flag["phase"]})
            except Exception:  # noqa: BLE001
                pass
            flightrec.record(
                "spmd.straggler", seq=seq, node=str(flag["node"]),
                phase=flag["phase"], ratio=flag.get("ratio") or 0,
                seconds=flag["seconds"])

    def summary(self):
        """Compact roll-up for /status?observability=true: serve mode,
        step-lifecycle counters, stream health, mesh-cache stats."""
        occ = self.occupancy()
        return {
            "serve_mode": self.serve_mode,
            "steps": {
                "announced": self.steps_announced,
                "entered": self.steps_entered,
                "exited": self.steps_exited,
                "last_seq": self.last_seq,
                "batch": self.batch_steps,
                "fused": self.fused_steps,
            },
            "queries": {
                "fused": self.fused_queries,
                "forwarded": self.forwarded,
                "fallbacks": self.fallbacks,
            },
            "stream": {
                "errors": self.stream_errors,
                "resyncs": self.stream_resyncs,
                "queue_depth": occ["queue_depth"],
                "seq_lag": occ["seq_lag"],
                "gap_onsets": occ["gap_onsets"],
                "gap_stall_seconds": occ["gap_stall_seconds"],
            },
            "straggler_flags": self.straggler_flags_total,
            "mesh_cache": self.mesh_cache.stats(),
        }

    def incident_snapshot(self):
        """Postmortem-bundle payload (utils/incident.py `spmd`
        collector): the full debug snapshot plus this node's step ring
        and, best-effort, the merged cross-node timeline — captured
        while a collective_stall is still open, so the bundle shows
        WHERE every peer was when the stream wedged."""
        snap = self.debug_snapshot()
        snap["steps_local"] = self.steps_local(limit=64)
        try:
            snap["timeline"] = self.steps_timeline(limit=16)
        except Exception as e:  # noqa: BLE001 — never fail the bundle
            snap["timeline_error"] = str(e)
        return snap

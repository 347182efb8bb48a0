"""Tracing: global-tracer indirection with nop default.

Reference: tracing/tracing.go:27-75 (GlobalTracer var + StartSpanFromContext)
and the opentracing adapter wired by cmd/server.go:78-93. Here the same
shape: a process-global `Tracer` defaulting to nop, spans started on every
executor/API hot path, and trace context propagated across nodes via HTTP
headers (reference: http/handler.go extractTracing / http/client.go inject).

Backends: `NopTracer` (default) and `InMemoryTracer` (tests, `--tracing
memory`, /debug inspection). With the nop tracer installed and nothing
live on the calling thread (no profiled query, no continued remote trace)
`start_span` hands back one shared no-op object: no Span exists, no clock
is read.

A live span records, besides its wall duration, the CPU its thread burned
(`time.thread_time()`), and at finish its `self` time and `self` CPU: its
own less what its same-thread children covered (children report into the
parent as they finish). At 32 callers a stage's wall is mostly waiting for
the interpreter lock; the CPU is what the stage costs the host. Finished
spans are summed by name into `span_stats()` (GET /debug/vars `spans`).

A live span also holds a `jax.profiler.TraceAnnotation` of its name, so
under a profiler session (`jax.profiler.start_trace`) every stage is a
host event on the thread that ran it, on the clock of the device's
operations. JAX is never imported from here: a process that has not
imported it cannot be under its profiler.
"""

import sys
import threading
import time
from collections import OrderedDict
from random import getrandbits

TRACE_HEADER = "X-Pilosa-Trace-Id"
PARENT_HEADER = "X-Pilosa-Span-Id"

_local = threading.local()


_trace_annotation = None


def _annotation_class():
    """`jax.profiler.TraceAnnotation`, or None in a process that has not
    imported JAX (looked up once it has)."""
    global _trace_annotation
    if _trace_annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            _trace_annotation = profiler.TraceAnnotation
    return _trace_annotation


def annotate(name):
    """Context manager marking `name` in the profiler's trace and nothing
    else: for the loops that run beside requests (cache flush, oplog
    sync, ingest merge, snapshot queue), a few times a second at most.
    Under half a microsecond with no profiler session."""
    cls = _annotation_class()
    return _NOOP if cls is None else cls(name)


class Span:
    """One timed operation. Finished spans carry duration, CPU and tags.

    `start` is wall-clock (for display and cross-node alignment);
    `duration` is measured on the monotonic clock so NTP steps and
    operator clock changes cannot corrupt it — durations feed both the
    profile tree and the skew estimator, which assumes they are real
    elapsed time. `cpu` is the thread-CPU clock's difference on the
    thread that ran the span; `self_time` / `self_cpu` are the span's own
    less its same-thread children's.

    The CPU clock is a system call, and on the chip's host a slow and a
    coarse one (6 to 13 µs a read, advancing in 10 ms ticks: one span's
    `cpu` there reads 0 or 0.01, and only sums over many spans mean
    anything). So a span may go unclocked (`cpu_weight` 0: `cpu` and
    `self_cpu` stay None), and a clocked one counts `cpu_weight` times in
    the per-name sums: utils/profile.py clocks one query in
    CPU_SAMPLE and weighs it by as much. A span inherits its parent's
    weight. The reads themselves are CPU the clocked request spends and
    the others do not, so the sums take them off again (count_span):
    `_reads` counts the reads its same-thread descendants made inside
    it, `_nkids` those descendants that are its direct children.

    As a context manager a span is the thread's active span from
    `__enter__` to `__exit__`, which finishes and publishes it."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "tags",
                 "start", "duration", "cpu", "self_time", "self_cpu",
                 "cpu_weight", "_t0", "_c0", "_kids", "_kids_cpu",
                 "_reads", "_nkids", "_parent", "_tid", "_prev", "_ann")

    def __init__(self, name, trace_id, span_id, parent_id, tags,
                 parent=None, cpu_weight=1):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.tags = tags
        self.duration = self.cpu = self.self_time = self.self_cpu = None
        self._kids = self._kids_cpu = 0.0
        self._reads = self._nkids = 0
        self._parent = parent
        self.cpu_weight = cpu_weight if parent is None \
            else parent.cpu_weight
        self._tid = threading.get_ident()
        self._prev = None
        cls = _trace_annotation or _annotation_class()
        if cls is None:
            self._ann = None
        else:
            self._ann = cls(name)
            self._ann.__enter__()
        self.start = time.time()
        self._c0 = time.thread_time() if self.cpu_weight else None
        self._t0 = time.perf_counter()

    @classmethod
    def from_dict(cls, d):
        """Rebuild a (finished) span from its to_dict shape — used when the
        coordinator merges spans fetched from remote nodes."""
        span = cls.__new__(cls)
        span.name = d.get("name", "")
        span.trace_id = d.get("traceID")
        span.span_id = d.get("spanID")
        span.parent_id = d.get("parentID")
        span.tags = dict(d.get("tags") or {})
        span.start = d.get("start")
        span.duration = d.get("duration")
        span.cpu = d.get("cpu")
        span.self_time = d.get("self")
        span.self_cpu = d.get("selfCpu")
        span._parent = span._ann = None
        span._reads = span._nkids = 0
        return span

    def set_tag(self, key, value):
        self.tags[key] = value

    def finish(self):
        """Stop the clocks (on the thread that started the span) and
        report into the parent. Idempotent."""
        if self.duration is not None:
            return
        duration = time.perf_counter() - self._t0
        if self._c0 is not None:
            cpu = self.cpu = max(0.0, time.thread_time() - self._c0)
            self.self_cpu = max(0.0, cpu - self._kids_cpu)
        else:
            cpu = 0.0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self.duration = duration
        self.self_time = max(0.0, duration - self._kids)
        parent = self._parent
        if parent is not None and parent._tid == self._tid \
                and parent.duration is None:
            parent._kids += duration
            parent._kids_cpu += cpu
            if self._c0 is not None:
                parent._reads += self._reads + 2
                parent._nkids += 1

    def __enter__(self):
        self._prev = getattr(_local, "span", None)
        _local.span = self
        return self

    def __exit__(self, *exc):
        if self.duration is None:
            _local.span = self._prev
            self.finish()
            _publish(self)
        return False

    def to_dict(self):
        """JSON shape for /debug/traces and query profiles."""
        return {"name": self.name, "traceID": self.trace_id,
                "spanID": self.span_id, "parentID": self.parent_id,
                "tags": dict(self.tags), "start": self.start,
                "duration": self.duration, "cpu": self.cpu,
                "self": self.self_time, "selfCpu": self.self_cpu}


class _NoSpan:
    """What `start_span` returns when nothing is live: one shared object,
    `with ... as span` binds None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoSpan()


class NopTracer:
    """Default tracer: allocates nothing, records nothing."""

    def on_finish(self, span):
        pass


class InMemoryTracer:
    """Collects finished spans in a bounded ring — the OLDEST spans are
    evicted past max_spans, so /debug/traces always shows recent activity
    on a long-lived server (trace retention); for tests and debugging."""

    def __init__(self, max_spans=10000):
        self.max_spans = max_spans
        self.spans = []
        self._lock = threading.Lock()

    def on_finish(self, span):
        with self._lock:
            self.spans.append(span)
            if len(self.spans) > self.max_spans:
                del self.spans[:len(self.spans) - self.max_spans]

    def find(self, name):
        with self._lock:
            return [s for s in self.spans if s.name == name]

    def to_dicts(self):
        """JSON dump for GET /debug/traces, oldest first."""
        with self._lock:
            return [s.to_dict() for s in self.spans]

    def clear(self):
        with self._lock:
            self.spans.clear()


class TraceIndex:
    """Finished spans indexed by trace id in a bounded two-level ring:
    at most `max_traces` trace ids retained (oldest-touched evicted), at
    most `max_spans_per_trace` spans per trace (later spans dropped and
    counted). This is the per-node half of cross-node trace assembly —
    the coordinator pulls a remote node's slice of a trace via
    GET /debug/traces/{trace_id}?local=true and merges it into one tree.

    Always on, but free on the default path: under the NopTracer with no
    incoming trace context no Span objects exist to index (see
    start_span's nop-fast path), so the index only ever sees spans from
    profiled / explicitly traced queries."""

    def __init__(self, max_traces=256, max_spans_per_trace=256):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self._traces = OrderedDict()  # trace_id -> [Span, ...]
        self._lock = threading.Lock()
        self.dropped_spans = 0
        self.evicted_traces = 0

    def add(self, span):
        if span.trace_id is None:
            return
        with self._lock:
            spans = self._traces.get(span.trace_id)
            if spans is None:
                spans = self._traces[span.trace_id] = []
            else:
                self._traces.move_to_end(span.trace_id)
            if len(spans) < self.max_spans_per_trace:
                spans.append(span)
            else:
                self.dropped_spans += 1
            while len(self._traces) > self.max_traces:
                self._traces.popitem(last=False)
                self.evicted_traces += 1

    def get(self, trace_id):
        """Finished spans of one trace as dicts (oldest-started first),
        or [] when unknown/evicted."""
        with self._lock:
            spans = list(self._traces.get(trace_id, ()))
        return [s.to_dict() for s in spans]

    def stats(self):
        with self._lock:
            return {"traces": len(self._traces),
                    "maxTraces": self.max_traces,
                    "maxSpansPerTrace": self.max_spans_per_trace,
                    "droppedSpans": self.dropped_spans,
                    "evictedTraces": self.evicted_traces}

    def clear(self):
        with self._lock:
            self._traces.clear()
            self.dropped_spans = 0
            self.evicted_traces = 0


_global_tracer = NopTracer()
_nop_tracer = True

# Secondary finished-span consumer (utils/profile.py registers its
# per-query router here). Separate from the tracer so query profiling
# works with the nop tracer still installed.
_span_sink = None

# Per-node finished-span index for cross-node assembly. Module-level and
# always present (zero-cost when no spans are created — see class doc).
_trace_index = TraceIndex()

# Finished live spans summed by name (GET /debug/vars `spans`): what an
# operator reads after sampling a few ?profile=true queries, and how the
# benchmark reads the CPU a stage costs.
_span_stats = {}
_span_stats_lock = threading.Lock()


def set_tracer(tracer):
    """Install the process-global tracer (reference: tracing.go SetGlobal)."""
    global _global_tracer, _nop_tracer
    _global_tracer = tracer if tracer is not None else NopTracer()
    _nop_tracer = isinstance(_global_tracer, NopTracer)


def get_tracer():
    return _global_tracer


def recording():
    """True when the installed tracer keeps spans (`--tracing memory`):
    then every request opens its edge span."""
    return not _nop_tracer


def set_span_sink(sink):
    global _span_sink
    _span_sink = sink


def trace_index():
    return _trace_index


def configure_trace_index(max_traces=256, max_spans_per_trace=256):
    """Resize (and reset) the per-node trace index; max_traces=0 disables
    retention entirely (spans still flow to the tracer/sink)."""
    global _trace_index
    _trace_index = TraceIndex(max_traces=max_traces,
                              max_spans_per_trace=max_spans_per_trace)
    return _trace_index


def index_span(span):
    """Feed one finished span into the trace index (also called by
    profile.finish for the query root span, which bypasses start_span)."""
    if _trace_index.max_traces > 0:
        _trace_index.add(span)


def get_trace(trace_id):
    """This node's finished spans for one trace id, as dicts."""
    return _trace_index.get(trace_id)


_clock_cost = None


def clock_read_cost():
    """CPU seconds one read of the thread-CPU clock costs its thread: the
    least per-read wall over a few short batches, measured once. On the
    chip's host a read is a system call of 6 to 13 µs."""
    global _clock_cost
    if _clock_cost is None:
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(100):
                time.thread_time()
            best = min(best, (time.perf_counter() - t0) / 100)
        _clock_cost = best
    return _clock_cost


def count_span(span):
    """Add one finished span to the per-name sums (its CPU `cpu_weight`
    times: it stands for that many spans that went unclocked), less what
    the clock's reads cost inside it: its own two reads' halves and every
    read of its same-thread descendants in `cpu`; its own and a read's
    worth for each direct child in `self_cpu`. Weighed, those reads would
    stand for reads the unclocked requests never made: a clocked Count's
    36 reads at 10 µs are ≈ 0.3 ms a query of CPU that is not there."""
    with _span_stats_lock:
        row = _span_stats.get(span.name)
        if row is None:
            row = _span_stats[span.name] = [0, 0.0, 0.0, 0.0, 0.0]
        row[0] += 1
        row[1] += span.duration
        row[2] += span.self_time
        if span.cpu is not None:
            cost = clock_read_cost()
            row[3] += (span.cpu - cost * (span._reads + 1)) \
                * span.cpu_weight
            row[4] += (span.self_cpu - cost * (span._nkids + 1)) \
                * span.cpu_weight


def span_stats():
    """{span name: {count, seconds, self_seconds, cpu_seconds,
    self_cpu_seconds}} over every live span finished in this process."""
    with _span_stats_lock:
        rows = {name: list(row) for name, row in _span_stats.items()}
    return {name: dict(zip(("count", "seconds", "self_seconds",
                            "cpu_seconds", "self_cpu_seconds"), row))
            for name, row in sorted(rows.items())}


def _publish(span):
    """A finished span goes to the tracer, to its query's profile, into
    the trace index and into the per-name sums."""
    _global_tracer.on_finish(span)
    if _span_sink is not None:
        _span_sink(span)
    index_span(span)
    count_span(span)


def _new_id():
    return "%016x" % getrandbits(64)


def new_trace_id():
    return _new_id()


def current_span():
    return getattr(_local, "span", None)


class with_span:
    """Adopt `span` as the active context on THIS thread (for worker
    threads continuing a request's trace; does not finish the span)."""

    __slots__ = ("span", "_prev")

    def __init__(self, span):
        self.span = span

    def __enter__(self):
        self._prev = getattr(_local, "span", None)
        _local.span = self.span
        return self.span

    def __exit__(self, *exc):
        _local.span = self._prev
        return False


def start_span(name, **tags):
    """Start a child of the current thread's active span (or a new trace).
    Use as `with start_span(...) as span:`.

    With the nop tracer installed and no active span on this thread this
    returns the one shared no-op object, whose `as` target is None: no
    Span is allocated and no clock read.
    """
    parent = getattr(_local, "span", None)
    if parent is None:
        if _nop_tracer:
            return _NOOP
        return Span(name, _new_id(), _new_id(), None, tags)
    return Span(name, parent.trace_id, _new_id(), parent.span_id, tags,
                parent)


def end_current(name):
    """Finish the thread's active span now if it is called `name`: for a
    stage that ends where the next one begins and not where a block does
    (`exec.plan` runs from execute_call to the first stack lookup; a
    nested call's plan ends with it). The `with` that opened it then
    exits as a no-op."""
    span = getattr(_local, "span", None)
    while span is not None and span.name == name \
            and span._tid == threading.get_ident():
        span.__exit__(None, None, None)
        span = getattr(_local, "span", None)


# -- cross-node propagation (reference: handler extractTracing / client
#    inject) ---------------------------------------------------------------

def inject_headers(headers=None):
    """Add trace context headers for an outgoing internal request."""
    headers = dict(headers or {})
    span = current_span()
    if span is not None:
        headers[TRACE_HEADER] = span.trace_id
        headers[PARENT_HEADER] = span.span_id
    return headers


def _header_get(headers, name):
    """Case-insensitive header lookup. http.server's Message headers are
    already case-insensitive, but plain dicts (tests, proxies that
    lowercase header names per HTTP/2) are not — fall back to a scan."""
    value = headers.get(name)
    if value is not None:
        return value
    want = name.lower()
    for k in headers:
        if isinstance(k, str) and k.lower() == want:
            return headers[k]
    return None


def span_from_headers(name, headers, **tags):
    """Continue a remote trace from incoming HTTP headers (case-insensitive
    lookup — see _header_get); without them, `start_span`."""
    trace_id = _header_get(headers, TRACE_HEADER)
    if trace_id is None:
        return start_span(name, **tags)
    return Span(name, trace_id, _new_id(),
                _header_get(headers, PARENT_HEADER), tags)


def continue_remote(span, headers):
    """Move a live span, opened before its request's headers were read,
    and the child it has open, into the remote trace the headers carry
    (if they carry one)."""
    trace_id = _header_get(headers, TRACE_HEADER)
    if trace_id is None:
        return
    span.trace_id = trace_id
    span.parent_id = _header_get(headers, PARENT_HEADER)
    child = current_span()
    if child is not None and child._parent is span:
        child.trace_id = trace_id


# -- cross-node assembly (Dapper, Sigelman et al. 2010 §5) ------------------
#
# Remote nodes timestamp spans with THEIR wall clock. The coordinator
# estimates each node's clock offset from the fan-out request it sent:
# for a request dispatched at local wall time t_send that returned at
# t_recv, the remote handler span covering it ran [r_start, r_end] in
# remote wall time. Assuming symmetric network delay (NTP's assumption):
#
#     theta = ((r_start - t_send) + (r_end - t_recv)) / 2
#
# is the remote clock minus the local clock; subtracting theta from
# every remote span start places it on the coordinator's timeline. When
# several request/response pairs exist for one node, the pair with the
# smallest round-trip envelope (t_recv - t_send) bounds theta tightest
# and wins. Durations are never adjusted — they are monotonic-clock
# measurements and already comparable across nodes.

def estimate_skew(local_spans, remote_spans):
    """Estimate one remote node's clock offset (remote - local, seconds).

    `local_spans`: span dicts recorded on this node (the fan-out client
    spans among them). `remote_spans`: span dicts fetched from the
    remote node. A pairing is any remote span whose parentID is a local
    span's spanID — i.e. the remote server span directly under our
    client span. Returns 0.0 when no pairing exists (spans merge
    uncorrected rather than not at all)."""
    by_id = {s["spanID"]: s for s in local_spans
             if s.get("spanID") and s.get("duration") is not None}
    best = None  # (rtt, theta)
    for r in remote_spans:
        local = by_id.get(r.get("parentID"))
        if local is None or r.get("duration") is None:
            continue
        t_send, t_recv = local["start"], local["start"] + local["duration"]
        r_start, r_end = r["start"], r["start"] + r["duration"]
        theta = ((r_start - t_send) + (r_end - t_recv)) / 2.0
        rtt = local["duration"]
        if best is None or rtt < best[0]:
            best = (rtt, theta)
    return best[1] if best else 0.0


def merge_remote_spans(local_spans, remote_by_node):
    """Merge per-node remote span dicts into the local timeline.

    Returns (all_spans, skew_by_node): remote starts are shifted by each
    node's estimated offset, every remote span is tagged with its node
    id, and duplicates (same spanID) are dropped. `remote_by_node` maps
    node id -> list of span dicts as returned by get_trace()."""
    seen = {s["spanID"] for s in local_spans if s.get("spanID")}
    merged = list(local_spans)
    skew_by_node = {}
    for node_id, spans in remote_by_node.items():
        theta = estimate_skew(local_spans, spans)
        skew_by_node[node_id] = theta
        for s in spans:
            if s.get("spanID") in seen:
                continue
            seen.add(s.get("spanID"))
            s = dict(s)
            if s.get("start") is not None:
                s["start"] = s["start"] - theta
            tags = dict(s.get("tags") or {})
            tags.setdefault("node", node_id)
            s["tags"] = tags
            merged.append(s)
    return merged, skew_by_node


def assemble_tree(spans):
    """Build the span forest from flat span dicts: children nested under
    their parentID when present, orphans become roots. Children sort by
    corrected start time. Returns the list of root nodes."""
    nodes = {}
    for s in spans:
        n = dict(s)
        n["children"] = []
        nodes[s["spanID"]] = n
    roots = []
    for s in spans:
        n = nodes[s["spanID"]]
        parent = nodes.get(s.get("parentID"))
        if parent is not None and parent is not n:
            parent["children"].append(n)
        else:
            roots.append(n)

    def _sort(children):
        children.sort(key=lambda c: (c.get("start") or 0.0))
        for c in children:
            _sort(c["children"])
    _sort(roots)
    return roots

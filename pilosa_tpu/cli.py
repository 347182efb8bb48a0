"""Command-line interface (reference: cmd/ + ctl/ — cobra commands).

Subcommands mirror the reference CLI (cmd/root.go:71-78): server, import,
backup, restore, export, inspect, check, generate-config, and config
(prints the EFFECTIVE merged configuration). Config comes from TOML file,
PILOSA_TPU_* env vars, and flags (reference: server/config.go precedence).
"""

import argparse
import json
import os
import signal
import sys
import time


DEFAULT_CONFIG = {
    "bind": "127.0.0.1:10101",
    "data-dir": "~/.pilosa_tpu",
    "max-op-n": 10000,
    "cluster": {"coordinator": True, "nodes": []},
    "anti-entropy": {"interval": "10m"},
}


def load_config(path=None):
    """TOML file < env < flags (reference: server/config.go)."""
    config = json.loads(json.dumps(DEFAULT_CONFIG))  # deep copy
    if path:
        import tomllib

        with open(path, "rb") as f:
            config.update(tomllib.load(f))
    if os.environ.get("PILOSA_TPU_BIND"):
        config["bind"] = os.environ["PILOSA_TPU_BIND"]
    if os.environ.get("PILOSA_TPU_DATA_DIR"):
        config["data-dir"] = os.environ["PILOSA_TPU_DATA_DIR"]
    return config


def cmd_server(args):
    from .core import Holder
    from .server import API, PilosaHTTPServer

    config = _apply_server_flags(load_config(args.config), args)
    host, _, port = config["bind"].partition(":")
    data_dir = os.path.expanduser(config["data-dir"])

    # Size the shared host-work pool before anything can submit to it
    # (default min(32, cpu); workers=1 == serial execution).
    if config.get("workers") is not None:
        from .utils import workpool

        workpool.configure(int(config["workers"]))

    # SPMD pod mode: join the global JAX distributed system BEFORE anything
    # can initialize a backend (utils/device.boot below is the first thing
    # that does). Process id = this node's position in the (identical on
    # every node) --cluster-hosts list; the coordinator service lives on
    # the first listed host.
    spmd_requested = bool(config.get("spmd"))
    if spmd_requested and not config.get("cluster-hosts"):
        raise SystemExit("--spmd requires --cluster-hosts")
    if spmd_requested:
        from .cluster.spmd import SpmdDataPlane

        spmd_hosts = [h.strip() for h in
                      config["cluster-hosts"].split(",") if h.strip()]
        local_ref = config.get("node-id") or config["bind"]
        if local_ref.startswith("http"):
            local_ref = local_ref.split("//", 1)[1]
        norm = [h.split("//", 1)[1] if h.startswith("http") else h
                for h in spmd_hosts]
        if local_ref not in norm:
            raise SystemExit(
                f"--spmd: node id {local_ref!r} not in --cluster-hosts")
        coord_host = norm[0].rsplit(":", 1)[0]
        coord_port = int(config.get("spmd-port", 27121))
        SpmdDataPlane.initialize(
            coordinator_address=f"{coord_host}:{coord_port}",
            num_processes=len(norm),
            process_id=norm.index(local_ref),
            cpu_collectives=config.get("spmd-cpu-collectives"))

    # Resolve the backend (a TPU, or the host CPU only when JAX_PLATFORMS=cpu
    # says so — anything else exits non-zero here, before serving), place
    # the compile cache and log what this node runs on.
    from .utils import device as _device

    _device.boot()

    # Durability: fault points arm from the env BEFORE any fsync/replay
    # code runs (a crash harness must be able to hit boot-time points),
    # and the node-wide fsync policy is set BEFORE fragments open so the
    # very first appended op already honors it.
    from .storage import oplog as _oplog_mod
    from .utils import faultpoints as _faultpoints

    _faultpoints.configure_from_env()
    storage_cfg = config.get("storage", {}) if isinstance(
        config.get("storage", {}), dict) else {}
    _oplog_mod.set_fsync_policy(
        storage_cfg.get("fsync", "never"),
        interval=storage_cfg.get("fsync-interval"))

    holder = Holder(data_dir, max_op_n=config.get("max-op-n")).open()

    oplog = None
    if storage_cfg.get("oplog", True):
        from .utils.logger import StandardLogger as _OplogLogger

        seg_bytes = storage_cfg.get("oplog-segment-bytes")
        oplog = _oplog_mod.OpLog(
            os.path.join(data_dir, "oplog"),
            segment_max_bytes=int(seg_bytes) if seg_bytes
            else _oplog_mod.DEFAULT_SEGMENT_BYTES,
            logger=_OplogLogger()).open()

    # Cluster bootstrap: static host list (the JAX-distributed model —
    # hosts known up front; reference: gossip seeds server/config.go), OR
    # dynamic join (--join): discover the existing cluster from a seed
    # node and register through the coordinator's resize flow (reference:
    # gossip join retry gossip/gossip.go:116-140 + nodeJoin
    # cluster.go:1796).
    cluster = None
    monitor = None
    join_needed = False
    hosts = config.get("cluster-hosts")
    join_target = getattr(args, "join", None) or config.get("join")
    if join_target and hosts:
        raise SystemExit("--join and --cluster-hosts are mutually exclusive")
    if join_target:
        from .cluster import Cluster, HealthMonitor, Node
        from .server import Client

        seed_uri = join_target if join_target.startswith("http") \
            else f"http://{join_target}"
        status = None
        last = None
        for _ in range(30):  # the seed may still be booting
            try:
                status = Client(seed_uri, timeout=5).status()
                break
            except Exception as e:
                last = e
                time.sleep(1.0)
        if status is None:
            raise SystemExit(
                f"cannot reach join target {join_target}: {last}")
        if any(not isinstance(d.get("uri"), str)
               for d in status.get("nodes", [])):
            raise SystemExit(
                f"join target {join_target} is not clustered "
                "(started without --cluster-hosts)")
        local_id = config.get("node-id") or config["bind"]
        if local_id.startswith("http"):
            local_id = local_id.split("//", 1)[1]
        join_host = local_id.rsplit(":", 1)[0]
        if join_host in ("0.0.0.0", "::", "") or ":" not in local_id:
            raise SystemExit(
                "--join registers this node's id as its reachable URI; "
                f"{local_id!r} is not reachable — pass --node-id "
                "host:port with a routable host")
        nodes = [Node.from_json(d) for d in status["nodes"]]
        cluster = Cluster(
            nodes=nodes, local_id=local_id,
            replica_n=int(status.get("replicaN", 1)), path=data_dir)
        # The seed's membership is AUTHORITATIVE: a stale on-disk
        # .topology (e.g. this node was removed while down) must not
        # shadow it, or we'd skip re-registration and serve with a
        # divergent ring. A restarted member appears in the seed's list
        # and skips registration naturally.
        join_needed = cluster.node(local_id) is None
        cluster.save_topology()
        monitor = HealthMonitor(cluster, Client).start()
    elif hosts:
        from .cluster import Cluster, HealthMonitor, Node
        from .server import Client

        host_list = [h.strip() for h in hosts.split(",") if h.strip()]
        nodes = []
        for h in host_list:
            uri = h if h.startswith("http") else f"http://{h}"
            nodes.append(Node(id=uri.split("//", 1)[1], uri=uri))
        # node identity: --node-id wins (needed when binding 0.0.0.0),
        # else derived from --bind
        local_id = config.get("node-id") or config["bind"]
        if local_id.startswith("http"):
            local_id = local_id.split("//", 1)[1]
        if not any(n.id == local_id for n in nodes):
            raise SystemExit(
                f"node id {local_id!r} not in --cluster-hosts; pass "
                f"--node-id matching one of the listed hosts")
        cluster = Cluster(
            nodes=nodes, local_id=local_id,
            replica_n=int(config.get("replicas", 1)), path=data_dir)
        cluster.load_topology()
        cluster.save_topology()
        monitor = HealthMonitor(cluster, Client).start()

    # Slow-query threshold (reference: long-query-time server/config.go);
    # unset disables the log. Write-batch cap (reference:
    # max-writes-per-request server/config.go); <=0 disables. Both already
    # flag-merged by _apply_server_flags.
    lqt = config.get("long-query-time")
    mwpr = config.get("max-writes-per-request", 0)
    # Streaming ingest engine: interval 0 — the default — keeps the
    # legacy apply-then-invalidate write path byte-identical.
    imi = config.get("ingest-merge-interval")
    ingest_interval = parse_duration(str(imi)) if imi else 0.0
    # Admission control (QoS): off — the default — keeps the legacy
    # uncontrolled serving path byte-identical.
    admission = str(config.get("admission", "off")).lower()
    adm_cap = config.get("admission-capacity")
    adm_qd = config.get("admission-queue-depth")
    adm_qt = config.get("admission-queue-timeout")
    spmd = None
    if spmd_requested and cluster is not None:
        from .cluster import spmd as spmd_mod
        from .cluster.spmd import SpmdDataPlane
        from .server import Client as _SpmdClient

        from .utils.logger import StandardLogger

        sgt = config.get("spmd-stream-gap-timeout")
        spmd = SpmdDataPlane(holder, cluster, _SpmdClient,
                             logger=StandardLogger(),
                             serve_mode=str(
                                 config.get("spmd-serve", "off")).lower(),
                             stream_gap_timeout=parse_duration(str(sgt))
                             if sgt else None)
        # mesh observatory: expose the serving plane to the incident
        # `spmd` collector and hang the pipeline-occupancy gauges on the
        # process stats client (one long-lived plane per server process)
        spmd_mod.set_active_plane(spmd)
        spmd.register_gauges()
    api = API(holder, cluster=cluster,
              long_query_time=parse_duration(lqt) if lqt else None,
              max_writes_per_request=int(mwpr),
              spmd=spmd, oplog=oplog,
              ingest_interval=ingest_interval,
              admission=admission,
              admission_capacity=float(adm_cap) if adm_cap else None,
              admission_queue_depth=int(adm_qd) if adm_qd else None,
              admission_queue_timeout=parse_duration(str(adm_qt))
              if adm_qt else None)
    anti_entropy = None
    translate_repl = None
    if cluster is not None:  # even single-node: the cluster can grow
        from .server import Client as _Client
        from .server.syncer import AntiEntropyMonitor, HolderSyncer
        from .server.translate_sync import TranslateReplicator

        interval = parse_duration(
            config.get("anti-entropy", {}).get("interval", "10m"))
        anti_entropy = AntiEntropyMonitor(
            HolderSyncer(holder, cluster, _Client), interval).start()
        # BEFORE serving: replica stores must be read-only from the first
        # request, or a keyed import could allocate ids that diverge from
        # the primary's
        translate_repl = TranslateReplicator(
            holder, cluster, _Client).start()
    # Metrics backend + runtime sampler (reference: server.go:419 stats
    # selection; server.go:813 monitorRuntime).
    from .utils.stats import RuntimeMonitor, build_stats

    stats = build_stats(
        getattr(args, "stats", None) or config.get("stats"),
        statsd_host=getattr(args, "statsd_host", None)
        or config.get("statsd-host"))
    runtime_monitor = RuntimeMonitor(
        stats, interval=parse_duration(
            config.get("metric-poll-interval", "10s"))).start()

    # Black-box flight recorder + stall watchdog + crash stack dumps.
    # The recorder defaults on (bounded ring, negligible cost); the
    # watchdog only runs when a deadline is configured.
    from .utils import flightrec as _flightrec
    from .utils.logger import StandardLogger as _FrLogger

    frs = config.get("flight-recorder-size")
    if frs is not None:
        _flightrec.configure(int(frs))
    wd_deadline = config.get("watchdog-deadline")
    if wd_deadline:
        _flightrec.configure_watchdog(
            parse_duration(str(wd_deadline)), logger=_FrLogger())
    _flightrec.install_crash_handler(logger=_FrLogger())

    # Device-link health prober: tiny canary dispatches through the real
    # dispatch-lock path drive /readyz + the query fail-fast gate.
    # Opt-in like the watchdog — when unset, the module guarantees zero
    # canary dispatches and /readyz reports DISABLED (ready).
    _devhealth = None
    probe_interval = config.get("device-probe-interval")
    if probe_interval:
        from .utils import devhealth as _devhealth

        probe_deadline = config.get("device-probe-deadline")
        _devhealth.configure(
            interval=parse_duration(str(probe_interval)),
            deadline=parse_duration(str(probe_deadline))
            if probe_deadline else _devhealth.DEFAULT_DEADLINE,
            logger=_FrLogger())

    # EXPLAIN ANALYZE plan retention + misestimate threshold
    # (exec/plan.py module state, like the flight recorder above).
    prs = config.get("plan-ring-size")
    emf = config.get("explain-misestimate-factor")
    if prs is not None or emf is not None:
        from .exec import plan as _plan

        _plan.configure(
            ring_size=int(prs) if prs is not None else None,
            misestimate_factor=float(emf) if emf is not None else None)

    # Container representation policy (ops/containers.py module state):
    # "auto" lets the per-fragment chooser pick dense/sparse/rle by
    # measured density; forcing "dense" is the bit-identical escape
    # hatch. Validated here so a typo fails startup, not first query.
    crepr = config.get("container-repr")
    if crepr is not None:
        from .ops import containers as _containers

        _containers.configure(str(crepr))

    # Adaptive execution engine (exec/adaptive.py module state): "on"
    # closes the cost-model/heat loop into strategy, tiling, and cache
    # policy; "shadow" computes-and-logs decisions without acting; the
    # default "off" keeps every legacy path byte-for-byte. Validated
    # here so a typo fails startup, not first query.
    amode = config.get("adaptive")
    if amode is not None:
        from .exec import adaptive as _adaptive

        _adaptive.configure(mode=str(amode))

    # Whole-plan fusion (exec/fusion.py module state): "on" traces
    # eligible queries into ONE jitted program cached by workload
    # fingerprint; "shadow" counts what would fuse but compiles
    # nothing; the default "off" keeps the legacy per-call loop
    # byte-for-byte. Validated here so a typo fails startup, not
    # first query.
    fmode = config.get("fusion")
    fcache = config.get("fusion-cache-size")
    fhits = config.get("fusion-min-hits")
    if fmode is not None or fcache is not None or fhits is not None:
        from .exec import fusion as _fusion

        _fusion.configure(
            mode=str(fmode) if fmode is not None else None,
            cache_size=int(fcache) if fcache is not None else None,
            min_hits=int(fhits) if fhits is not None else None)

    # SLO objectives: error-budget burn rate over the existing timing
    # histograms (utils/workload.py module state). Accepts a repeated
    # --slo flag (list) or a comma-separated string from the config file.
    slo_cfg = config.get("slo")
    if slo_cfg:
        from .utils import workload as _workload

        if isinstance(slo_cfg, str):
            slo_specs = [s.strip() for s in slo_cfg.split(",") if s.strip()]
        else:
            slo_specs = []
            for item in slo_cfg:
                slo_specs.extend(
                    s.strip() for s in str(item).split(",") if s.strip())
        burn = config.get("slo-burn-threshold")
        _workload.configure_slo(
            slo_specs,
            burn_threshold=float(burn) if burn is not None else None,
            logger=_FrLogger())

    # Trace retention (GET /debug/traces): "memory" installs a bounded
    # InMemoryTracer ring; the default keeps the nop tracer, whose hot
    # path allocates no spans at all (query profiles via ?profile=true /
    # long-query-time work either way).
    if config.get("tracing") == "memory":
        from .utils import tracing as _tracing

        _tracing.set_tracer(_tracing.InMemoryTracer(
            max_spans=int(config.get("trace-max-spans", 10000))))

    # Incident autopsy (utils/incident.py module state): opt-in writer of
    # anomaly-triggered postmortem bundles (devhealth DOWN, watchdog
    # stall, SLO burn, deadline storms, SIGTERM). Without --incident-dir
    # every hook site is one module-global check.
    inc_dir = config.get("incident-dir")
    if inc_dir:
        from .utils import incident as _incident

        inc_max = config.get("incident-max")
        _incident.configure(
            str(inc_dir),
            max_incidents=int(inc_max) if inc_max is not None
            else _incident.DEFAULT_MAX_INCIDENTS,
            logger=_FrLogger())
        # bundle surfaces that live on instances, not modules
        _incident.register_collector(
            "oplog",
            lambda: (dict(api.oplog.summary(), enabled=True)
                     if getattr(api, "oplog", None) is not None
                     else {"enabled": False}))
        _incident.register_collector("admission", api.admission_stats)
        # which phase the dispatches wedged in: the evaluator's own table
        # (GET /debug/dispatch)
        _local_ex = getattr(api.executor, "local", api.executor)
        if hasattr(_local_ex, "dispatch_phase_stats"):
            _incident.register_collector(
                "dispatch", _local_ex.dispatch_phase_stats)

    # Metrics exemplars: timing histograms keep one recent trace id per
    # bucket, exposed in OpenMetrics exemplar syntax on /metrics and in
    # /debug/slo. Opt-in; the disabled path is one flag check.
    if config.get("metrics-exemplars"):
        from .utils import stats as _stats_mod

        _stats_mod.configure_exemplars(
            True, registry=_stats_mod.registry_of(stats))

    # Diagnostics phone-home: opt-in only, requires an explicit endpoint
    # (reference: diagnostics.go + server.go:760; default ON there, OFF
    # here — no default public endpoint).
    diagnostics = None
    diag_cfg = config.get("diagnostics", {})
    if isinstance(diag_cfg, dict) and diag_cfg.get("enabled") \
            and diag_cfg.get("endpoint"):
        from .server.diagnostics import Diagnostics
        from .utils.logger import StandardLogger

        diagnostics = Diagnostics(
            api, diag_cfg["endpoint"],
            interval=parse_duration(diag_cfg.get("interval", "1h")),
            logger=StandardLogger()).start()

    # TLS + CORS come from the MERGED config only — _apply_server_flags
    # already folded the flags in, so `pilosa_tpu config` output is
    # exactly what runs here (reference: handler.allowed-origins
    # server/config.go:75).
    tls_cfg = config.get("tls", {}) if isinstance(
        config.get("tls", {}), dict) else {}
    origins = config.get("handler", {}).get("allowed-origins", []) \
        if isinstance(config.get("handler", {}), dict) else []
    if isinstance(origins, str):  # scalar TOML value / comma-joined flag
        origins = origins.split(",")
    origins = [o.strip() for o in origins if o.strip()]
    # Crash recovery BEFORE serving: re-apply acked writes the previous
    # process died holding, so the first query already sees them.
    if oplog is not None:
        replayed = api.replay_oplog()
        if replayed:
            print(f"oplog: replayed {replayed} record(s) after unclean "
                  "shutdown", flush=True)

    server = PilosaHTTPServer(
        api, host=host, port=int(port or 10101), stats=stats,
        tls_cert=tls_cfg.get("certificate"),
        tls_key=tls_cfg.get("key"),
        allowed_origins=origins)
    server.start()
    if join_needed:
        # Register with the coordinator now that we can serve the resize
        # instruction (schema + streamed fragments land over HTTP). Retries
        # cover a busy coordinator (resize already in progress) — the
        # reference's join loop does the same (gossip.go:116-140).
        import threading as _threading

        own_scheme = "https" if tls_cfg.get("certificate") else "http"

        def _join():
            from .cluster import Node as _JNode
            from .server import Client as _JClient

            own_uri = f"{own_scheme}://{cluster.local_id}"
            for attempt in range(60):
                coord = cluster.coordinator
                if coord is not None:
                    try:
                        _JClient(coord.uri).resize_add_node(
                            cluster.local_id, own_uri)
                        print(f"joined cluster via {coord.id}", flush=True)
                        return
                    except Exception as e:
                        if "already in cluster" in str(e):
                            return
                # coordinatorship may have moved since the status
                # snapshot: refresh membership from any live node
                if attempt % 5 == 4:
                    for peer in list(cluster.nodes):
                        try:
                            st = _JClient(peer.uri, timeout=5).status()
                            cluster.nodes = sorted(
                                (_JNode.from_json(d)
                                 for d in st["nodes"]),
                                key=lambda n: n.id)
                            break
                        except Exception:
                            continue
                time.sleep(2.0)
            print("ERROR: cluster join did not complete after 120s — "
                  "this node is serving OUTSIDE the cluster (owns no "
                  "shards; writes here are invisible to members). Retry "
                  "by restarting with --join.", flush=True)

        _threading.Thread(target=_join, daemon=True,
                          name="cluster-join").start()
    if server.tls_cert:
        # SIGHUP rotates the TLS keypair without a restart (reference:
        # keypairReloader server/tlsconfig.go:68-90 installs the same
        # signal hook); a bad new keypair keeps the old one serving.
        def _reload_tls(signum, frame):
            try:
                server.reload_tls()
                print("SIGHUP: reloaded TLS certificate and key",
                      flush=True)
            except Exception as e:
                print(f"SIGHUP: keeping old TLS keypair "
                      f"(reload failed: {e})", flush=True)

        signal.signal(signal.SIGHUP, _reload_tls)
    extra = f", cluster of {len(cluster.nodes)}" if cluster else ""
    print(f"pilosa_tpu server listening on {server.address} "
          f"(data: {data_dir}{extra})", flush=True)
    # SIGINT is the graceful stop (the finally block below). A server
    # started from a non-interactive shell (`server &`, a supervisor)
    # inherits SIGINT ignored and Python then leaves it ignored — so ask
    # for the KeyboardInterrupt explicitly.
    signal.signal(signal.SIGINT, signal.default_int_handler)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        if diagnostics:
            diagnostics.stop()
        if _devhealth is not None:
            _devhealth.stop()
        from .utils import incident as _incident_mod

        _incident_mod.stop()
        _flightrec.stop_watchdog()
        runtime_monitor.stop()
        if translate_repl:
            translate_repl.stop()
        if anti_entropy:
            anti_entropy.stop()
        if monitor:
            monitor.stop()
        server.stop()
        api.close()
        holder.close()
        if oplog is not None:
            # AFTER holder.close(): fragments are synced and closed, so
            # the shutdown checkpoint can bless everything applied
            oplog.close()
    return 0


def parse_duration(s):
    """'10m', '30s', '500ms', '1h30m' -> seconds (reference: toml.Duration,
    Go time.ParseDuration forms)."""
    import re

    s = str(s).strip()
    units = {"ns": 1e-9, "us": 1e-6, "ms": 1e-3, "s": 1, "m": 60, "h": 3600}
    parts = re.findall(r"(\d+(?:\.\d+)?)(ns|us|ms|s|m|h)", s)
    if not parts:
        return float(s)
    consumed = "".join(n + u for n, u in parts)
    if consumed != s:
        raise ValueError(f"invalid duration: {s!r}")
    return sum(float(n) * units[u] for n, u in parts)


def cmd_import(args):
    """CSV bulk import via the HTTP API (reference: ctl/import.go)."""
    import csv as csv_mod

    from .server import Client

    client = Client(args.host)
    if args.create:
        try:
            client.create_index(args.index)
        except Exception:
            pass
        try:
            options = {}
            if args.field_type == "int":
                options = {"type": "int", "min": args.min, "max": args.max}
            elif args.field_type == "time":
                options = {"type": "time", "timeQuantum": args.time_quantum}
            client.create_field(args.index, args.field, options)
        except Exception:
            pass

    # keyed imports: detect row/column keys from the live schema like the
    # reference (ctl/import.go useRowKeys/useColumnKeys from field/index
    # options). A FAILED schema fetch aborts loudly — guessing "unkeyed"
    # could import numeric-looking keys as raw ids onto wrong columns.
    use_row_keys = use_col_keys = False
    try:
        schema = client.schema()
    except Exception as e:
        raise SystemExit(f"import: cannot fetch schema from {args.host}: {e}")
    for idx_desc in schema.get("indexes", []):
        if idx_desc["name"] != args.index:
            continue
        use_col_keys = bool(
            idx_desc.get("options", {}).get("keys", False))
        for f_desc in idx_desc.get("fields", []):
            if f_desc["name"] == args.field:
                use_row_keys = bool(
                    f_desc.get("options", {}).get("keys", False))

    rows, cols, values, stamps = [], [], [], []
    total = 0
    source = open(args.file) if args.file != "-" else sys.stdin
    try:
        reader = csv_mod.reader(source)
        for rnum, record in enumerate(reader, 1):
            if not record:
                continue
            try:
                if args.field_type == "int":
                    cols.append(record[0] if use_col_keys
                                else int(record[0]))
                    values.append(int(record[1]))
                else:
                    rows.append(record[0] if use_row_keys
                                else int(record[0]))
                    cols.append(record[1] if use_col_keys
                                else int(record[1]))
                    # optional 3rd column: timestamp — TIME fields only
                    # (reference format "2006-01-02T15:04",
                    # ctl/import.go:234); other field types ignore extra
                    # columns, as the pre-timestamp CLI did
                    stamps.append(
                        record[2] if args.field_type == "time"
                        and len(record) > 2 and record[2] else None)
            except (ValueError, IndexError) as e:
                raise SystemExit(
                    f"import: invalid record on line {rnum}: "
                    f"{record!r} ({e})")
            if len(cols) >= args.batch_size:
                total += _flush_import(client, args, rows, cols, values,
                                       stamps, use_row_keys, use_col_keys)
                rows, cols, values, stamps = [], [], [], []
        if cols:
            total += _flush_import(client, args, rows, cols, values,
                                   stamps, use_row_keys, use_col_keys)
    finally:
        if source is not sys.stdin:
            source.close()
    print(f"imported: {total} changed bits")
    return 0


def _flush_import(client, args, rows, cols, values, stamps,
                  use_row_keys, use_col_keys):
    # Client treats None key lists as absent, so the keys-vs-ids split is
    # one conditional per axis
    column_keys = cols if use_col_keys else None
    if args.field_type == "int":
        out = client.import_values(args.index, args.field, cols, values,
                                   column_keys=column_keys)
    else:
        timestamps = stamps if any(s is not None for s in stamps) else None
        out = client.import_bits(
            args.index, args.field, rows, cols, timestamps=timestamps,
            row_keys=rows if use_row_keys else None,
            column_keys=column_keys)
    return out.get("changed", 0) if isinstance(out, dict) else 0


def cmd_backup(args):
    """Archive an index (schema + every fragment's roaring blob) from a
    live server into a tar file (reference: fragment.WriteTo tar archives
    fragment.go:2436-2607 + ctl backup tooling)."""
    import io
    import tarfile

    from .server import Client

    def make_client(url):
        return Client(url, tls_skip_verify=args.tls_skip_verify,
                      ca_cert=args.tls_ca)

    client = make_client(args.host)
    schema = client.schema()
    indexes = [i for i in schema.get("indexes", [])
               if args.index is None or i["name"] == args.index]
    if args.index is not None and not indexes:
        raise SystemExit(f"index not found: {args.index}")

    # Internal fragment endpoints are node-local; on a cluster, walk every
    # node so shards held only by peers are captured too (a single-node
    # backup of a cluster would otherwise be silently partial).
    clients = [client]
    for node in client.nodes():
        uri = node.get("uri")
        if uri and uri.rstrip("/") != client.base_url:
            clients.append(make_client(uri))

    def add(tar, name, data):
        info = tarfile.TarInfo(name)
        info.size = len(data)
        tar.addfile(info, io.BytesIO(data))

    # Write to a temp name and publish only on success so a refused or
    # crashed backup never leaves a plausible-looking partial archive at
    # --output (same temp+rename discipline as fragment snapshots).
    tmp_out = args.output + ".partial"
    n_frags = 0
    unreachable = []
    with tarfile.open(tmp_out, "w") as tar:
        add(tar, "schema.json",
            json.dumps({"indexes": indexes}).encode())
        for idx in indexes:
            iname = idx["name"]
            seen = set()
            for c in clients:
                # a node can fail at ANY of the three fetches; every
                # failure routes through the same unreachable gate
                try:
                    shards = c.index_shards(iname).get("shards", [])
                    for shard in shards:
                        frags = c.shard_fragments(
                            iname, shard).get("fragments", [])
                        for frag in frags:
                            name = (f"{iname}/{frag['field']}"
                                    f"/{frag['view']}/{shard}")
                            if name in seen:
                                continue
                            data = c.fragment_data(
                                iname, frag["field"], frag["view"], shard)
                            seen.add(name)
                            add(tar, name, data)
                            n_frags += 1
                except Exception as e:
                    unreachable.append(f"{c.base_url} ({e})")
    if unreachable:
        # An unreachable node may hold shards no replica covers; there is
        # no way to verify coverage without it, so don't pretend the
        # archive is complete (reference behavior: backups are node-exact).
        print(f"warning: node(s) unreachable during backup: "
              f"{sorted(set(unreachable))}; archive may be missing their "
              f"exclusively-held shards", file=sys.stderr)
        if not args.allow_partial:
            os.unlink(tmp_out)
            raise SystemExit(
                "refusing to write a possibly-partial backup "
                "(pass --allow-partial to accept)")
    os.replace(tmp_out, args.output)
    print(f"backed up {len(indexes)} index(es), {n_frags} fragment(s) "
          f"to {args.output}")
    return 0


def cmd_restore(args):
    """Restore a backup tar into a live server: schema first, then each
    fragment via the import-roaring fast path (reference: fragment.ReadFrom
    + api.ImportRoaring api.go:368)."""
    import tarfile

    from .server import Client

    client = Client(args.host, tls_skip_verify=args.tls_skip_verify,
                    ca_cert=args.tls_ca)
    n_frags = 0
    with tarfile.open(args.input) as tar:
        schema_member = tar.getmember("schema.json")
        schema = json.loads(tar.extractfile(schema_member).read())
        client._request("POST", "/schema", json.dumps(schema).encode())
        for member in tar.getmembers():
            if member.name == "schema.json" or not member.isfile():
                continue
            index, field, view, shard = member.name.split("/")
            client.import_roaring(
                index, field, int(shard), tar.extractfile(member).read(),
                view=view)
            n_frags += 1
    print(f"restored {n_frags} fragment(s) from {args.input}")
    return 0


def cmd_export(args):
    """(reference: ctl/export.go)"""
    from .server import Client

    client = Client(args.host)
    shards = range(args.shards) if args.shards else None
    if shards is None:
        status = client._request("GET", "/internal/shards/max")
        max_shard = status.get("standard", {}).get(args.index, 0)
        shards = range(max_shard + 1)
    for shard in shards:
        sys.stdout.write(client.export_csv(args.index, args.field, shard))
    return 0


def cmd_inspect(args):
    """Dump fragment bit counts from a data file (reference:
    ctl/inspect.go)."""
    from .roaring import deserialize

    with open(args.path, "rb") as f:
        data = f.read()
    bitmap, flags, ops = deserialize(data)
    print(f"file: {args.path}")
    print(f"flags: {flags}  ops-replayed: {ops}")
    print(f"containers: {len(bitmap.keys())}  bits: {bitmap.count()}")
    from .shardwidth import CONTAINERS_PER_SHARD

    rows = {}
    for key in bitmap.keys():
        row = key // CONTAINERS_PER_SHARD
        rows[row] = rows.get(row, 0) + bitmap.containers[key].n
    for row in sorted(rows):
        print(f"  row {row}: {rows[row]} bits")
    return 0


def cmd_check(args):
    """Consistency-check fragment files (reference: ctl/check.go)."""
    from .roaring import FormatError, deserialize

    failed = 0
    for path in args.paths:
        try:
            with open(path, "rb") as f:
                bitmap, _, _ = deserialize(f.read())
            for key in bitmap.keys():
                c = bitmap.containers[key]
                if c.n != c._count():
                    raise FormatError(
                        f"container {key}: cardinality mismatch")
            print(f"{path}: ok")
        except Exception as e:
            failed += 1
            print(f"{path}: FAILED — {e}")
    return 1 if failed else 0


def _toml_value(v):
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, (int, float)):
        return str(v)
    if isinstance(v, list):
        return "[" + ", ".join(_toml_value(x) for x in v) + "]"
    if isinstance(v, dict):  # inline table (e.g. [[cluster.nodes]] entries)
        inner = ", ".join(f"{k} = {_toml_value(v[k])}" for k in sorted(v))
        return "{" + inner + "}"
    return json.dumps(str(v))


def _apply_server_flags(config, args):
    """Fold server-command flags into a loaded config — the single merge
    used by BOTH `server` and `config`, so what `config` prints is exactly
    what `server` runs with (reference: cmd/root.go setAllConfig does this
    once via viper for every subcommand)."""
    for flag in ("bind", "data_dir", "cluster_hosts", "node_id",
                 "replicas", "spmd_port", "spmd_serve",
                 "spmd_cpu_collectives", "spmd_stream_gap_timeout",
                 "long_query_time",
                 "max_writes_per_request", "tracing", "workers",
                 "flight_recorder_size", "watchdog_deadline",
                 "incident_dir", "incident_max", "metrics_exemplars",
                 "plan_ring_size", "explain_misestimate_factor",
                 "device_probe_interval", "device_probe_deadline",
                 "slo", "slo_burn_threshold",
                 "container_repr", "adaptive",
                 "fusion", "fusion_cache_size", "fusion_min_hits",
                 "ingest_merge_interval",
                 "admission", "admission_capacity",
                 "admission_queue_depth", "admission_queue_timeout"):
        val = getattr(args, flag, None)
        if val is not None:
            config[flag.replace("_", "-")] = val
    if getattr(args, "spmd", False):
        config["spmd"] = True
    # TLS and CORS live in config sub-tables ([tls], [handler]); fold the
    # flags into those tables so `config` prints them where the server
    # reads them (reference: server/config.go TLS + handler sections).
    if getattr(args, "tls_certificate", None) is not None \
            or getattr(args, "tls_key", None) is not None:
        tls = config.get("tls")
        if not isinstance(tls, dict):
            tls = config["tls"] = {}
        if getattr(args, "tls_certificate", None) is not None:
            tls["certificate"] = args.tls_certificate
        if getattr(args, "tls_key", None) is not None:
            tls["key"] = args.tls_key
    if getattr(args, "allowed_origins", None) is not None:
        handler = config.get("handler")
        if not isinstance(handler, dict):
            handler = config["handler"] = {}
        handler["allowed-origins"] = args.allowed_origins
    # Durability knobs live in [storage] — ONE fsync policy shared by the
    # write-ahead oplog and the fragment WALs (a split policy would make
    # the documented durability level a lie at whichever layer is weaker).
    if getattr(args, "fsync", None) is not None \
            or getattr(args, "no_oplog", False) \
            or getattr(args, "oplog_segment_bytes", None) is not None:
        storage = config.get("storage")
        if not isinstance(storage, dict):
            storage = config["storage"] = {}
        if getattr(args, "fsync", None) is not None:
            storage["fsync"] = args.fsync
        if getattr(args, "no_oplog", False):
            storage["oplog"] = False
        if getattr(args, "oplog_segment_bytes", None) is not None:
            storage["oplog-segment-bytes"] = args.oplog_segment_bytes
    return config


def cmd_config(args):
    """Print the EFFECTIVE merged configuration — file < env < flags — as
    TOML (reference: cmd/root.go:71-78 registers ctl/config.go, whose Run
    marshals the fully-populated server.Config that viper merged from all
    three sources). `generate-config` prints defaults; this prints what
    the server would actually run with."""
    config = _apply_server_flags(load_config(args.config), args)
    from .shardwidth import EXPONENT

    config.setdefault("shard-width-exponent", EXPONENT)
    scalars = {k: v for k, v in config.items() if not isinstance(v, dict)}
    tables = {k: v for k, v in config.items() if isinstance(v, dict)}
    for key in sorted(scalars):
        print(f"{key} = {_toml_value(scalars[key])}")
    for name in sorted(tables):
        print()
        print(f"[{name}]")
        for key in sorted(tables[name]):
            print(f"{key} = {_toml_value(tables[name][key])}")
    return 0


def cmd_holder(args):
    """Open the data directory, load everything, shut down (reference:
    cmd/server.go:33-57 newHolderCmd — 'only useful for diagnostic use':
    proves the on-disk state loads cleanly and shows what is in it)."""
    from .core import Holder

    config = _apply_server_flags(load_config(args.config), args)
    data_dir = os.path.expanduser(config["data-dir"])
    if not os.path.isdir(data_dir):
        # a diagnostic must not create (and then bless) a mistyped path
        print(f"holder: data directory does not exist: {data_dir}",
              file=sys.stderr)
        return 1
    holder = Holder(data_dir).open()
    try:
        n_frags = sum(1 for _ in holder._all_fragments())
        print(f"holder loaded: {data_dir}")
        print(f"indexes: {len(holder.indexes)}  "
              f"fields: {sum(len(i.fields) for i in holder.indexes.values())}  "
              f"fragments: {n_frags}")
        for idx in sorted(holder.indexes.values(), key=lambda i: i.name):
            fields = ", ".join(
                f"{f.name}({f.type})"
                for f in sorted(idx.fields.values(), key=lambda f: f.name))
            print(f"  {idx.name}: {fields}")
    finally:
        holder.close()
    return 0


def cmd_generate_config(args):
    """(reference: ctl/generate_config.go) Print default TOML config."""
    print('bind = "127.0.0.1:10101"')
    print('data-dir = "~/.pilosa_tpu"')
    print("max-op-n = 10000")
    print()
    print("[cluster]")
    print("coordinator = true")
    print("nodes = []")
    print()
    print('[anti-entropy]')
    print('interval = "10m"')
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="pilosa_tpu", description="TPU-native distributed bitmap index")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("server", help="run the server daemon")
    p.add_argument("--cluster-hosts", default=None,
                   help="comma-separated host:port list of ALL cluster "
                        "nodes (static bootstrap); omit for single-node")
    p.add_argument("--node-id", default=None,
                   help="this node's id (defaults to host:port of --bind)")
    p.add_argument("--join", default=None,
                   help="host:port of ANY existing cluster node: discover "
                        "the cluster from it and join dynamically via the "
                        "coordinator's resize flow (mutually exclusive "
                        "with --cluster-hosts)")
    p.add_argument("--replicas", type=int, default=None,
                   help="replication factor (default 1)")
    p.add_argument("--spmd", action="store_true", default=False,
                   help="join a global JAX distributed system across the "
                        "cluster: coverable Count merges ride collectives "
                        "(ICI/DCN on TPU pods, gloo on CPU) instead of the "
                        "HTTP data plane")
    p.add_argument("--spmd-port", type=int, default=None,
                   help="TCP port of the JAX distributed coordinator "
                        "service on the FIRST --cluster-hosts node "
                        "(default 27121)")
    p.add_argument("--spmd-serve", default=None,
                   choices=("off", "on", "shadow"),
                   help="mesh-resident SPMD serving: off (default) keeps "
                        "the legacy per-query collective side-channel "
                        "byte-identical; on promotes the mesh to the "
                        "primary data plane (cached sharded stacks, "
                        "step-stream announcements, batched + fused "
                        "collective steps); shadow serves legacy while "
                        "probing the mesh cache for divergence")
    p.add_argument("--spmd-cpu-collectives", default=None,
                   choices=("none", "gloo"),
                   help="CPU-backend collective implementation for "
                        "--spmd (gloo enables real cross-process CPU "
                        "collectives, e.g. the 2-process test harness; "
                        "default none)")
    p.add_argument("--spmd-stream-gap-timeout", default=None,
                   help="how long a peer's step-stream runner waits on "
                        "a sequence gap before resyncing past it "
                        "(duration, default 30s); gap ONSET fires the "
                        "spmd.stream_gap flightrec event and a "
                        "collective_stall incident bundle immediately")
    p.add_argument("--bind", default=None)
    p.add_argument("--data-dir", default=None)
    p.add_argument("--config", default=None)
    p.add_argument("--long-query-time", default=None,
                   help="log queries slower than this duration "
                        "(e.g. 500ms, 2s); disabled when unset")
    p.add_argument("--max-writes-per-request", type=int, default=None,
                   help="reject queries with more than this many write "
                        "calls (reference: max-writes-per-request); "
                        "<=0 disables")
    p.add_argument("--stats", default=None,
                   choices=["local", "statsd", "none"],
                   help="metrics backend (default local registry; statsd "
                        "also emits UDP datagrams)")
    p.add_argument("--tracing", default=None,
                   choices=["none", "memory"],
                   help="span retention: memory keeps a bounded ring of "
                        "finished spans served at /debug/traces "
                        "(default none: nop tracer, zero overhead)")
    p.add_argument("--statsd-host", default=None,
                   help="statsd host:port (default 127.0.0.1:8125)")
    p.add_argument("--tls-certificate", default=None,
                   help="PEM certificate file; serves HTTPS when set")
    p.add_argument("--tls-key", default=None, help="PEM key file")
    p.add_argument("--allowed-origins", default=None,
                   help="comma-separated CORS origins browsers may query "
                        "from ('*' allows all); no CORS headers when unset")
    p.add_argument("--workers", type=int, default=None,
                   help="host-side worker pool size for per-shard fan-out "
                        "(default min(32, cpu), env PILOSA_TPU_WORKERS; "
                        "1 = serial execution)")
    p.add_argument("--flight-recorder-size", type=int, default=None,
                   help="flight-recorder ring capacity in events "
                        "(default 2048; 0 disables recording)")
    p.add_argument("--watchdog-deadline", default=None,
                   help="stall watchdog deadline (e.g. 30s, 2m): dump "
                        "stacks + recorder tail when a dispatch or query "
                        "runs past it; disabled when unset")
    p.add_argument("--incident-dir", default=None,
                   help="directory for anomaly-triggered postmortem "
                        "bundles (flightrec dump, thread stacks, /debug "
                        "snapshots) written on devhealth DOWN, watchdog "
                        "stall, SLO burn, deadline storms, and SIGTERM; "
                        "served at /debug/incidents; disabled when unset")
    p.add_argument("--incident-max", type=int, default=None,
                   help="retained incident bundles before the oldest is "
                        "deleted (default 16)")
    p.add_argument("--metrics-exemplars", action="store_true",
                   default=None,
                   help="keep one recent trace id per timing-histogram "
                        "bucket and expose it in OpenMetrics exemplar "
                        "syntax on /metrics and in /debug/slo")
    p.add_argument("--plan-ring-size", type=int, default=None,
                   help="retained misestimated EXPLAIN ANALYZE plans "
                        "(GET /debug/plans; default 128, 0 disables "
                        "retention)")
    p.add_argument("--explain-misestimate-factor", type=float, default=None,
                   help="flag a plan node when actual cost deviates from "
                        "the estimate by more than this factor in either "
                        "direction (default 3.0)")
    p.add_argument("--device-probe-interval", default=None,
                   help="device-link canary probe interval (e.g. 1s, "
                        "500ms): background canary dispatches drive the "
                        "LIVE/DEGRADED/DOWN readiness state at /readyz "
                        "and /debug/device; disabled when unset")
    p.add_argument("--slo", action="append", default=None,
                   help="latency objective as name=threshold@quantile "
                        "(e.g. query=50ms@p99); repeatable. Tracked as "
                        "multi-window error-budget burn at /debug/slo "
                        "and slo_burn_rate gauges")
    p.add_argument("--slo-burn-threshold", type=float, default=None,
                   help="burn-rate multiple that must be exceeded in "
                        "BOTH the fast and slow windows before "
                        "slo.burn_alert fires (default 6.0)")
    p.add_argument("--device-probe-deadline", default=None,
                   help="per-canary deadline (e.g. 5s) before a probe "
                        "counts as a device-link failure (default 5s)")
    p.add_argument("--container-repr", default=None,
                   choices=["auto", "dense", "sparse", "rle"],
                   help="device container representation policy: auto "
                        "(default) picks dense/block-sparse/run-length "
                        "per fragment by measured density; dense forces "
                        "the legacy bit-identical planes; sparse/rle "
                        "force one compressed format where eligible")
    p.add_argument("--adaptive", default=None,
                   choices=["off", "on", "shadow"],
                   help="adaptive execution engine: on prices "
                        "stacked-vs-fallback, GroupBy tile shape, and "
                        "cache admission/eviction through the calibrated "
                        "cost model + fragment heat; shadow computes and "
                        "logs decisions without acting; off (default) "
                        "keeps the legacy static paths byte-for-byte")
    p.add_argument("--fusion", default=None,
                   choices=["off", "on", "shadow"],
                   help="whole-plan fusion: on traces an eligible "
                        "query's every top-level Count into ONE jitted "
                        "device program cached by workload fingerprint "
                        "(a cold fingerprint never pays a compile); "
                        "shadow counts what would fuse without "
                        "compiling; off (default) keeps the legacy "
                        "per-call loop byte-for-byte")
    p.add_argument("--fusion-cache-size", type=int, default=None,
                   help="bounded LRU of fused programs per process "
                        "(default 64); eviction drops the compiled "
                        "program, so re-entry re-compiles")
    p.add_argument("--fusion-min-hits", type=int, default=None,
                   help="completed queries a workload fingerprint needs "
                        "before its first fused trace+compile "
                        "(default 2); raise it when /debug/fusion shows "
                        "compiles outnumbering cache hits")
    p.add_argument("--ingest-merge-interval", default=None,
                   help="streaming ingest merge interval (e.g. 250ms): "
                        "import deltas buffer host-side (still "
                        "WAL-durable at ack) and fold into resident "
                        "device stacks in one batched donated merge per "
                        "interval; reads serve the pre-merge snapshot "
                        "meanwhile (default 0 = disabled, legacy "
                        "apply-then-invalidate path)")
    p.add_argument("--admission", default=None,
                   choices=["off", "on"],
                   help="cost-aware admission control + degradation "
                        "ladder: classifies queries (X-Query-Class / "
                        "PQL shape), prices them through the EXPLAIN "
                        "cost model, debits per-class token buckets, "
                        "queues bounded past capacity, and degrades "
                        "NORMAL→SHED_BATCH→STALE_OK→LIFEBOAT on SLO "
                        "burn / device health; off (default) keeps the "
                        "legacy uncontrolled serving path byte-identical")
    p.add_argument("--admission-capacity", type=float, default=None,
                   help="admission token refill rate in device-ms per "
                        "second (default 1000 = one device's worth); "
                        "split interactive/batch/internal 60/30/10")
    p.add_argument("--admission-queue-depth", type=int, default=None,
                   help="bounded admission queue per class: past it, "
                        "queries get 503 + Retry-After (default 64)")
    p.add_argument("--admission-queue-timeout", default=None,
                   help="max time a query waits for admission tokens "
                        "before 503 (e.g. 5s; default 5s)")
    p.add_argument("--fsync", default=None,
                   choices=["always", "interval", "never"],
                   help="durability fsync policy for the write-ahead "
                        "oplog AND fragment WALs ([storage] fsync; "
                        "default never): always = fsync before every "
                        "ack, interval = background fsync every ~50ms, "
                        "never = OS flush only")
    p.add_argument("--no-oplog", action="store_true", default=False,
                   help="disable the durable write-ahead oplog "
                        "([storage] oplog = false): acked writes held "
                        "only in memory are lost on crash")
    p.add_argument("--oplog-segment-bytes", type=int, default=None,
                   help="oplog segment rotation size in bytes "
                        "([storage] oplog-segment-bytes; default 64MiB); "
                        "rotation also triggers a checkpoint")
    p.set_defaults(fn=cmd_server)

    p = sub.add_parser("import", help="bulk-import CSV data")
    p.add_argument("--host", default="http://127.0.0.1:10101")
    p.add_argument("--index", required=True)
    p.add_argument("--field", required=True)
    p.add_argument("--create", action="store_true",
                   help="create index/field if missing")
    p.add_argument("--field-type", default="set",
                   choices=["set", "int", "time"])
    p.add_argument("--min", type=int, default=0)
    p.add_argument("--max", type=int, default=(1 << 31) - 1)
    p.add_argument("--time-quantum", default="YMD")
    p.add_argument("--batch-size", type=int, default=100_000)
    p.add_argument("file", help="CSV path or - for stdin")
    p.set_defaults(fn=cmd_import)

    def add_tls_flags(p):
        p.add_argument("--tls-skip-verify", action="store_true",
                       help="accept any server certificate")
        p.add_argument("--tls-ca", default=None,
                       help="PEM CA bundle for https servers")

    p = sub.add_parser("backup", help="archive index data from a server")
    p.add_argument("--host", default="http://127.0.0.1:10101")
    p.add_argument("--index", default=None,
                   help="index to back up (default: all)")
    p.add_argument("--output", required=True, help="tar file to write")
    p.add_argument("--allow-partial", action="store_true",
                   help="write the archive even when some cluster nodes "
                        "are unreachable")
    add_tls_flags(p)
    p.set_defaults(fn=cmd_backup)

    p = sub.add_parser("restore", help="restore a backup tar into a server")
    p.add_argument("--host", default="http://127.0.0.1:10101")
    p.add_argument("--input", required=True, help="tar file to read")
    add_tls_flags(p)
    p.set_defaults(fn=cmd_restore)

    p = sub.add_parser("export", help="export a field as CSV")
    p.add_argument("--host", default="http://127.0.0.1:10101")
    p.add_argument("--index", required=True)
    p.add_argument("--field", required=True)
    p.add_argument("--shards", type=int, default=None)
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("inspect", help="inspect a fragment data file")
    p.add_argument("path")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("check", help="consistency-check fragment files")
    p.add_argument("paths", nargs="+")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("generate-config", help="print default config TOML")
    p.set_defaults(fn=cmd_generate_config)

    p = sub.add_parser(
        "holder", help="open the data directory, load it, shut down "
                       "(diagnostic)")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--config", default=None)
    p.set_defaults(fn=cmd_holder)

    p = sub.add_parser(
        "config", help="print the effective merged config as TOML "
                       "(file < env < flags)")
    p.add_argument("--config", default=None)
    p.add_argument("--bind", default=None)
    p.add_argument("--data-dir", default=None)
    p.add_argument("--cluster-hosts", default=None)
    p.add_argument("--node-id", default=None)
    p.add_argument("--replicas", type=int, default=None)
    p.add_argument("--spmd", action="store_true", default=False)
    p.add_argument("--spmd-port", type=int, default=None)
    p.add_argument("--spmd-serve", default=None,
                   choices=("off", "on", "shadow"))
    p.add_argument("--spmd-cpu-collectives", default=None,
                   choices=("none", "gloo"))
    p.add_argument("--spmd-stream-gap-timeout", default=None)
    p.add_argument("--long-query-time", default=None)
    p.add_argument("--max-writes-per-request", type=int, default=None)
    p.add_argument("--tracing", default=None, choices=["none", "memory"])
    p.add_argument("--tls-certificate", default=None)
    p.add_argument("--tls-key", default=None)
    p.add_argument("--allowed-origins", default=None)
    p.add_argument("--workers", type=int, default=None)
    p.add_argument("--flight-recorder-size", type=int, default=None)
    p.add_argument("--watchdog-deadline", default=None)
    p.add_argument("--plan-ring-size", type=int, default=None)
    p.add_argument("--explain-misestimate-factor", type=float, default=None)
    p.add_argument("--device-probe-interval", default=None)
    p.add_argument("--device-probe-deadline", default=None)
    p.add_argument("--slo", action="append", default=None)
    p.add_argument("--slo-burn-threshold", type=float, default=None)
    p.add_argument("--container-repr", default=None,
                   choices=["auto", "dense", "sparse", "rle"])
    p.add_argument("--adaptive", default=None,
                   choices=["off", "on", "shadow"])
    p.add_argument("--fusion", default=None,
                   choices=["off", "on", "shadow"])
    p.add_argument("--fusion-cache-size", type=int, default=None)
    p.add_argument("--fusion-min-hits", type=int, default=None)
    p.add_argument("--fsync", default=None,
                   choices=["always", "interval", "never"])
    p.add_argument("--no-oplog", action="store_true", default=False)
    p.add_argument("--oplog-segment-bytes", type=int, default=None)
    p.set_defaults(fn=cmd_config)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())

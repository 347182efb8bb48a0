"""North-star benchmark: PQL Intersect+Count QPS on a 1B-column index.

BASELINE.json: "serve 1B-row Intersect+Count PQL at >=10x single-node CPU
QPS". The reference publishes no absolute numbers (BASELINE.md), so
vs_baseline is measured against a single-node CPU execution of the same
query implemented the fastest way numpy can (SIMD bitwise AND + popcount
over the identical dense planes) on this machine.

Serving model: the index is resident (the reference's mmap'd roaring in
RAM; here dense row planes in TPU HBM as one stacked [shards, words] array
per row). Every query is DISTINCT — query i intersects `a` with
`b ^ mask_i` (same bytes touched, different result; the scalar mask fuses
into the AND, unlike a jnp.roll shard rotation which XLA may materialize
as a full extra plane copy). A loaded server accumulates concurrent
queries into device batches: one dispatch answers a whole batch via vmap
over the masks, and XLA reuses each index tile across the batch — so a
batch of 256 distinct queries streams the index from HBM roughly once,
the TPU-idiomatic way to serve concurrent load.

Timing discipline: every timed region ends by materializing a scalar on
the host that depends on EVERY result, so the clock stops at end-to-end
completion and not at the enqueue.

Roofline (in "extra"):
- The kernel is memory-bound (~1 ALU op per 4 bytes): the ceiling is HBM
  bandwidth. `device_ms_per_query` comes from a fori_loop chain of K
  dependent queries inside ONE dispatch; `bytes_per_sec`/`pct_hbm_peak`
  derive from it.
- `dispatch_rtt_ms` is one trivial jit round trip; `p50_latency_ms` for a
  single synchronous query is that round trip plus one device pass.

One process: `main()` boots the device (pilosa_tpu/utils/device.py — a TPU,
or the host CPU only under an explicit JAX_PLATFORMS=cpu, else a non-zero
exit) and owns it for the whole run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.
"""

import json
import os
import sys
import time

import numpy as np

# HBM peak bandwidth, bytes/s, keyed by a substring of `device_kind`.
# Source: Google Cloud TPU documentation, system architecture page of each
# generation ("TPU v5e": 819 GB/s; "TPU v5p": 2765 GB/s; "TPU v4":
# 1228 GB/s; "TPU v6e": 1640 GB/s).
HBM_PEAK = {
    "v5 lite": 819e9,   # v5e (device_kind "TPU v5 lite")
    "v5litepod": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v6 lite": 1640e9,  # v6e (device_kind "TPU v6 lite")
    "v6e": 1640e9,
}


def cpu_popcount_sum(x):
    return int(np.sum(np.bitwise_count(x), dtype=np.int64))


def _hbm_peak(device):
    """Peak HBM bytes/s of `device`, or None on the host CPU (no HBM). A
    TPU whose device_kind is not in the table is an error, not a default:
    a roofline share against a guessed peak is worse than none."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in HBM_PEAK.items():
        if key in kind:
            return peak
    raise SystemExit(
        f"bench: no HBM peak on record for device_kind "
        f"{device.device_kind!r}; add it to HBM_PEAK with its source")


def _mask(i):
    """Per-query distinct uint32 mask (Knuth multiplicative hash)."""
    return np.uint32((i * 2654435761) & 0xFFFFFFFF)


def _fsync_mode():
    """Process-wide fsync policy (storage/oplog.py) tagged into every
    emitted record."""
    try:
        from pilosa_tpu.storage.oplog import fsync_policy

        return fsync_policy()
    except Exception:
        return None


def _adaptive_tag():
    """(mode, decision counters) of the adaptive engine for attempt
    tagging — in-process, the bench drives the executor directly."""
    try:
        from pilosa_tpu.exec import adaptive

        return adaptive.mode(), adaptive.decision_counts()
    except Exception:
        return None, None


def _fusion_tag():
    """(mode, decision counters) of the whole-plan fusion engine for
    attempt tagging — a run where queries traced into fused programs is
    only comparable to another run under the same --fusion policy."""
    try:
        from pilosa_tpu.exec import fusion

        return fusion.mode(), fusion.decision_counts()
    except Exception:
        return None, None


def _ingest_mode():
    """Streaming ingest engine mode ("off" or "interval=<n>s") tagged
    into every emitted record — write-path numbers are only comparable
    across runs measured under the same delta-buffer policy."""
    try:
        from pilosa_tpu.exec import ingest

        return ingest.mode()
    except Exception:
        return None


def _spmd_mode():
    """SPMD serve mode the numbers were measured under ("off"/"on"/
    "shadow"/"http") — a mesh-collective run pays one collective step
    per batch, an HTTP fan-out run pays one POST per shard owner, so
    serving comparisons must be like-for-like on the data plane too.
    The in-process bench runs no cluster, so this reads the env the
    spmd_serving suite leg set for the run."""
    return os.environ.get("PILOSA_TPU_SPMD_SERVE", "off")


def _admission_mode():
    """Admission mode ("off" or "on state=<rung>") tagged into every
    emitted record — a run measured while the degradation ladder was
    shedding is not comparable to an unloaded one."""
    try:
        from pilosa_tpu.server import admission

        return admission.mode()
    except Exception:
        return None


def main():
    from pilosa_tpu.utils import device as device_boot

    device_boot.boot()
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.shardwidth import SHARD_WIDTH, WORDS_PER_ROW

    device = jax.devices()[0]
    platform = device.platform
    peak = _hbm_peak(device)  # unknown device_kind fails before measuring
    n_columns = 1_000_000_000
    n_shards = (n_columns + SHARD_WIDTH - 1) // SHARD_WIDTH  # 954
    batch = 256
    n_batches = 8
    k_roof = 256
    if platform == "cpu":
        # CI/dev fallback: keep the shape, shrink the scale.
        n_shards = 32
        n_columns = n_shards * SHARD_WIDTH
        batch, n_batches, k_roof = 8, 2, 4

    # Build two ~50%-density row planes directly in device HBM (the
    # resident index), plus host copies for the CPU baseline and
    # correctness check.
    key = jax.random.PRNGKey(7)
    ka, kb = jax.random.split(key)
    shape = (n_shards, WORDS_PER_ROW)

    @jax.jit
    def gen(k):
        return jax.random.bits(k, shape, dtype=jnp.uint32)

    a = gen(ka)
    b = gen(kb)
    int(jnp.sum(a[:1].astype(jnp.int32)))  # force materialization

    from pilosa_tpu.parallel import QueryKernels

    # The shipped serving kernel (hi/lo split reduce, exact at any scale).
    got = int(QueryKernels.count_intersect(a, b))
    host_a = np.asarray(a[:8])
    host_b = np.asarray(b[:8])
    want_slice = cpu_popcount_sum(np.bitwise_and(host_a, host_b))
    got_slice = int(QueryKernels.count_intersect(a[:8], b[:8]))
    if got_slice != want_slice:
        print(json.dumps({"metric": "error", "value": 0, "unit": "",
                          "error": "correctness check failed"}))
        sys.exit(1)

    def _intersect_count(a, b, m):
        return jnp.sum(
            jax.lax.population_count(a & (b ^ m)).astype(jnp.int32))

    query = jax.jit(_intersect_count)
    query_batch = jax.jit(jax.vmap(_intersect_count, in_axes=(None, None, 0)))

    all_masks = np.array([_mask(i + 1) for i in range(batch * n_batches)])
    mask_batches = [jnp.asarray(all_masks[i * batch:(i + 1) * batch])
                    for i in range(n_batches)]
    int(query_batch(a, b, mask_batches[0])[0])  # compile + warm
    int(query(a, b, jnp.uint32(_mask(1))))       # compile the scalar path

    # Throughput: batched pipelined serving. All batches dispatch
    # asynchronously; the clock stops only after a scalar depending on
    # EVERY per-query result materializes on host.
    t0 = time.perf_counter()
    outs = [query_batch(a, b, mb) for mb in mask_batches]
    int(jnp.sum(jnp.stack([jnp.sum(o) for o in outs])))
    elapsed = time.perf_counter() - t0
    n_queries = batch * n_batches
    qps = n_queries / elapsed

    # Roofline: K queries chained with a data dependency inside ONE
    # dispatch (each iteration re-streams both planes; no tile reuse
    # possible, no host round trips) -> device compute per query and
    # achieved HBM bandwidth.
    @jax.jit
    def query_chain(a, b, masks):
        def body(i, acc):
            return acc + jnp.sum(
                jax.lax.population_count(
                    a & (b ^ (masks[i] ^ acc.astype(jnp.uint32) // 2**30))
                ).astype(jnp.int32))

        return jax.lax.fori_loop(0, k_roof, body, jnp.int32(0))

    chain_masks = jnp.asarray(all_masks[:k_roof])
    int(query_chain(a, b, chain_masks))  # compile + warm

    # dispatch round-trip floor (trivial jit + scalar fetch)
    @jax.jit
    def noop(x):
        return x + 1

    s0 = jnp.int32(1)
    int(noop(s0))
    rtts = []
    for _ in range(10):
        t0 = time.perf_counter()
        int(noop(s0))
        rtts.append(time.perf_counter() - t0)
    dispatch_rtt = float(np.percentile(rtts, 50))

    t0 = time.perf_counter()
    int(query_chain(a, b, chain_masks))
    chain_elapsed = max(time.perf_counter() - t0 - dispatch_rtt, 1e-9)
    device_s_per_query = chain_elapsed / k_roof
    bytes_per_query = 2 * n_shards * WORDS_PER_ROW * 4
    bytes_per_sec = bytes_per_query / device_s_per_query
    pct_hbm_peak = round(100 * bytes_per_sec / peak, 1) if peak else None

    # Latency: single synchronous query (worst-case turnaround: one
    # dispatch RTT + one device pass over the index).
    n_lat = 20 if platform != "cpu" else 5
    lat_samples = []
    for i in range(n_lat):
        t0 = time.perf_counter()
        int(query(a, b, jnp.uint32(_mask(5000 + i))))
        lat_samples.append(time.perf_counter() - t0)
    lat_ms = float(np.percentile(lat_samples, 50)) * 1000

    # Served-path companion: the SAME 1B-column-scale Intersect+Count
    # through the framework path (Holder -> Executor -> stacked serving
    # with group-commit fetches) under concurrent clients, published side
    # by side with the kernel qps above. A failure here fails the run.
    from bench_suite import measure_served_1b

    if platform == "cpu":
        # same shard count as the kernel leg so the two legs stay
        # comparable under the one metric label
        served = measure_served_1b(
            n_shards=n_shards, workers=4, n_queries=32)
    else:
        served = measure_served_1b()

    # CPU single-node baseline: identical distinct-query computation,
    # resident in RAM, vectorized numpy.
    host_a_full = np.asarray(a)
    host_b_full = np.asarray(b)
    reps = 3
    t0 = time.perf_counter()
    for i in range(reps):
        cpu_got = cpu_popcount_sum(np.bitwise_and(
            host_a_full, np.bitwise_xor(host_b_full, _mask(i + 1))))
    cpu_elapsed = time.perf_counter() - t0
    cpu_qps = reps / cpu_elapsed
    want = cpu_got
    got_dev = int(query(a, b, jnp.uint32(_mask(reps))))
    if want != got_dev:
        print(json.dumps({"metric": "error", "value": 0, "unit": "",
                          "error": "tpu/cpu result mismatch"}))
        sys.exit(1)

    # Headline = the better of kernel and served throughput. The served
    # path (full Holder->Executor->stacked stack with group-commit
    # dispatch batching) now EXCEEDS the bespoke kernel loop — fused
    # multi-query programs reuse hot leaf tiles across the batch — so the
    # client-visible number is also the best number; both are published.
    # Guard: the served leg only competes when it measured the SAME shard
    # count as the kernel leg (one metric label, one scale).
    served_qps = served.get("served_qps", 0.0) \
        if served.get("n_shards") == n_shards else 0.0
    best_qps = max(qps, served_qps)
    adaptive_mode, adaptive_decisions = _adaptive_tag()
    fusion_mode, fusion_decisions = _fusion_tag()
    print(json.dumps({
        "metric": f"pql_intersect_count_qps_{n_columns // 1_000_000}M_cols",
        "value": round(best_qps, 2),
        "unit": "qps",
        "vs_baseline": round(best_qps / cpu_qps, 2),
        "extra": {
            "kernel_qps": round(qps, 2),
            "platform": platform,
            # durability setting the numbers were measured under —
            # fsync=always trades ack latency for power-loss safety, so
            # comparisons across runs must be like-for-like
            "fsync_mode": _fsync_mode(),
            "device_kind": getattr(device, "device_kind", ""),
            "n_shards": n_shards,
            "batch_size": batch,
            "p50_latency_ms": round(lat_ms, 3),
            "dispatch_rtt_ms": round(dispatch_rtt * 1000, 3),
            "p50_minus_rtt_ms": round(lat_ms - dispatch_rtt * 1000, 3),
            "device_ms_per_query": round(device_s_per_query * 1000, 3),
            "bytes_per_query": bytes_per_query,
            "bytes_per_sec": round(bytes_per_sec),
            "hbm_peak_bytes_per_sec": peak,
            "pct_hbm_peak": pct_hbm_peak,
            "cpu_baseline_qps": round(cpu_qps, 2),
            "count": got,
            "served": served,
            # EXPLAIN plan shape of the served query (measured by the
            # served leg; surfaced here so plan regressions show up in
            # the headline record too)
            "plan_nodes": served.get("plan_nodes"),
            "plan_strategy": served.get("plan_strategy"),
            # top query shapes by frequency from the workload table —
            # the headline record names what the served leg actually ran
            "workload_top": served.get("workload_top"),
            "served_pct_of_kernel": round(
                100 * served["served_qps"] / qps, 1)
            if "served_qps" in served else None,
            # adaptive engine mode + decision counters: a regression
            # hunt must know whether (and how) the optimizer was
            # steering the run it is comparing against
            "adaptive_mode": adaptive_mode,
            "adaptive_decisions": adaptive_decisions,
            # whole-plan fusion mode + fuse/interpret counters: a fused
            # run pays one dispatch per query, an interpreted one pays
            # one per call — latency comparisons must be like-for-like
            "fusion_mode": fusion_mode,
            "fusion_decisions": fusion_decisions,
            # streaming ingest engine mode: write-path comparisons must
            # be like-for-like on the delta-buffer policy too
            "ingest_mode": _ingest_mode(),
            # admission mode + ladder rung: serving comparisons are only
            # valid between runs under the same QoS policy, and a run
            # measured while the ladder was shedding is tainted
            "admission_mode": _admission_mode(),
            # SPMD serve mode: which data plane (mesh collectives vs
            # HTTP fan-out) the serving numbers were measured on
            "spmd_mode": _spmd_mode(),
        },
    }))


if __name__ == "__main__":
    main()

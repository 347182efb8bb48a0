"""Kernel microbenchmarks — the reference's roaring µbench suite
re-expressed for dense bit-plane kernels.

The reference benchmarks IntersectionCount/union/difference/xor across
container-type PAIRS (array×run, bitmap×run, … — roaring_test.go:
1524-1747) because its kernels are per-type. Dense planes have one
representation, so the matrix here is density REGIME pairs (sparse ~50
bits, dense ~50%, runs) × ops, over a [shards, words] stack sized like a
working set (default 64 shards ≈ 64M columns), plus the BSI comparator
and sum kernels (fragment_internal_test.go:709-2461 benchmarks' shapes).

Timing discipline matches bench.py: measure a fori_loop CHAIN of K
dependent evaluations inside ONE dispatch, subtract one dispatch RTT,
divide by K — giving per-op device time free of host round trips. Each
benchmark prints one JSON line:
{"metric": "kernel_<op>_<regime>", "value": <ops/sec>, "unit": "ops/s",
 "extra": {...}}.

Usage: python bench_kernels.py [n_shards]. Needs a TPU; an explicit
JAX_PLATFORMS=cpu runs shrunken shapes on the host (utils/device.boot).
"""

import json
import sys
import time

import numpy as np


def _mk_regime(rng, n_shards, words, kind):
    if kind == "sparse":
        plane = np.zeros((n_shards, words), np.uint32)
        for s in range(n_shards):
            idx = rng.choice(words, size=50, replace=False)
            plane[s, idx] = rng.integers(1, 1 << 32, size=50,
                                         dtype=np.uint32)
        return plane
    if kind == "dense":
        return rng.integers(0, 1 << 32, (n_shards, words), dtype=np.uint32)
    # runs: long stretches of all-ones
    plane = np.zeros((n_shards, words), np.uint32)
    run = max(words // 8, 1)
    for s in range(n_shards):
        start = int(rng.integers(0, max(words - run, 1)))
        plane[s, start:start + run] = 0xFFFFFFFF
    return plane


def main():
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    device = jax.devices()[0]
    platform = device.platform
    n_shards = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    k_chain = 64
    if platform == "cpu":
        n_shards = min(n_shards, 8)
        k_chain = 8

    rng = np.random.default_rng(11)
    regimes = {kind: jnp.asarray(
        _mk_regime(rng, n_shards, WORDS_PER_ROW, kind))
        for kind in ("sparse", "dense", "runs")}

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
    rtt = float(np.percentile(rtts, 50))

    def chain_time(fn, a, b):
        """Per-op seconds for `fn(a, b) -> scalar`, via a K-long
        dependent chain in one dispatch (mask-perturbed so XLA cannot
        hoist the body out of the loop)."""

        @jax.jit
        def chain(a, b):
            def body(i, acc):
                m = (acc.astype(jnp.uint32) & jnp.uint32(1))
                return acc + fn(a ^ m, b)

            return jax.lax.fori_loop(0, k_chain, body, jnp.int32(0))

        int(chain(a, b))  # compile + warm
        t0 = time.perf_counter()
        int(chain(a, b))
        return max(time.perf_counter() - t0 - rtt, 1e-9) / k_chain

    def popcount_of(x):
        return jnp.sum(jax.lax.population_count(x).astype(jnp.int32))

    ops = {
        "intersect_count": lambda a, b: popcount_of(a & b),
        "union_count": lambda a, b: popcount_of(a | b),
        "difference_count": lambda a, b: popcount_of(a & ~b),
        "xor_count": lambda a, b: popcount_of(a ^ b),
    }

    bytes_per_plane = n_shards * WORDS_PER_ROW * 4
    for op_name, fn in ops.items():
        for ra, rb in (("sparse", "runs"), ("dense", "runs"),
                       ("dense", "dense"), ("sparse", "dense")):
            sec = chain_time(fn, regimes[ra], regimes[rb])
            print(json.dumps({
                "metric": f"kernel_{op_name}_{ra}x{rb}",
                "value": round(1.0 / sec, 1),
                "unit": "ops/s",
                "extra": {
                    "platform": platform,
                    "device_kind": getattr(device, "device_kind", ""),
                    "n_shards": n_shards,
                    "us_per_op": round(sec * 1e6, 1),
                    "bytes_per_op": 2 * bytes_per_plane,
                    "gbytes_per_sec": round(
                        2 * bytes_per_plane / sec / 1e9, 1),
                },
            }), flush=True)

    # BSI kernels (reference: fragment rangeOp/sum benchmarks): depth-12
    # planes, range_lt + filtered sum via the shipped kernel modules.
    from pilosa_tpu.ops import bsi

    depth = 12
    planes = jnp.asarray(rng.integers(
        0, 1 << 32, (depth, n_shards, WORDS_PER_ROW), dtype=np.uint32))
    exists = regimes["dense"]
    pbits = jnp.asarray(bsi.predicate_bits(1234, depth))

    def bsi_lt(planes, exists):
        # lt over the stacked planes; scalar result via popcount
        def per_shard(pl, ex):
            return jnp.sum(jax.lax.population_count(
                bsi.range_lt(pl, jnp.zeros_like(ex), ex, pbits,
                             False, False)).astype(jnp.int32))

        return jnp.sum(jax.vmap(per_shard, in_axes=(1, 0))(planes, exists))

    @jax.jit
    def bsi_chain(planes, exists):
        def body(i, acc):
            m = (acc.astype(jnp.uint32) & jnp.uint32(1))
            return acc + bsi_lt(planes, exists ^ m)

        return jax.lax.fori_loop(0, k_chain, body, jnp.int32(0))

    int(bsi_chain(planes, exists))
    t0 = time.perf_counter()
    int(bsi_chain(planes, exists))
    sec = max(time.perf_counter() - t0 - rtt, 1e-9) / k_chain
    print(json.dumps({
        "metric": "kernel_bsi_range_lt_depth12",
        "value": round(1.0 / sec, 1),
        "unit": "ops/s",
        "extra": {
            "platform": platform, "n_shards": n_shards, "depth": depth,
            "us_per_op": round(sec * 1e6, 1),
            "gbytes_per_sec": round(
                (depth + 1) * bytes_per_plane / sec / 1e9, 1),
        },
    }), flush=True)


def bsi_pallas_vs_jnp():
    """The measurement ops/pallas_kernels.py's PERF STATUS note calls
    for: fused Pallas BSI range kernel vs the shipped two-program jnp
    path, same [D=16, WORDS_PER_ROW] inputs, n>=30 dispatches,
    block_until_ready on the batch. Run on a REAL chip
    (`python bench_kernels.py bsi-pallas`); prints one JSON line with
    both ms so the kernel can be promoted to default or retired."""
    import jax
    import jax.numpy as jnp

    # both paths are invoked explicitly below — the PILOSA_TPU_PALLAS
    # opt-in gate is not on this code path, so no env var is needed
    from pilosa_tpu.ops import bsi, pallas_kernels
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    device = jax.devices()[0]
    depth, n = 16, 30
    rng = np.random.default_rng(5)
    planes = jnp.asarray(rng.integers(
        0, 1 << 32, (depth, WORDS_PER_ROW), dtype=np.uint32))
    sign = jnp.zeros((WORDS_PER_ROW,), jnp.uint32)
    exists = jnp.asarray(rng.integers(
        0, 1 << 32, (WORDS_PER_ROW,), dtype=np.uint32))
    pbits = jnp.asarray(bsi.predicate_bits(12345, depth))

    # inputs as jit ARGUMENTS, not closure constants: closed-over arrays
    # are compile-time constants XLA may fold, which would time a
    # precomputed buffer fetch instead of the kernel
    jnp_fn = jax.jit(lambda p, s, e, pb: bsi._range_lt_jnp(
        p, s, e, pb, False, True))
    pallas_fn = jax.jit(lambda p, s, e, pb: pallas_kernels.bsi_range_mask(
        "lt", p, s, e, pb, False, True))

    args = (planes, sign, exists, pbits)
    got_a, got_b = np.asarray(jnp_fn(*args)), np.asarray(pallas_fn(*args))
    assert np.array_equal(got_a, got_b), "pallas/jnp mismatch"

    def measure(fn):
        fn(*args).block_until_ready()  # warm
        t0 = time.perf_counter()
        outs = [fn(*args) for _ in range(n)]
        for o in outs:
            o.block_until_ready()
        return (time.perf_counter() - t0) / n * 1000

    jnp_ms = measure(jnp_fn)
    pallas_ms = measure(pallas_fn)
    print(json.dumps({
        "metric": "bsi_range_lt_pallas_vs_jnp",
        "value": round(jnp_ms / pallas_ms, 3),
        "unit": "speedup_x",
        "extra": {
            "platform": device.platform,
            "device_kind": getattr(device, "device_kind", ""),
            "depth": depth, "n_dispatches": n,
            "jnp_ms": round(jnp_ms, 4),
            "pallas_ms": round(pallas_ms, 4),
        },
    }), flush=True)


def groupby_pairwise():
    """Recursive vs pairwise GroupBy inner product: R1*R2 per-combination
    count_intersect dispatches (the executor's old innermost recursion)
    against the tiled pairwise_counts matrix (one dispatch + one host
    sync per tile pair). Prints one JSON line with both wall times and
    both dispatch counts (`python bench_kernels.py groupby-pairwise
    [n_shards]`)."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitplane
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    device = jax.devices()[0]
    n_shards = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    if device.platform == "cpu":
        n_shards = min(n_shards, 4)
    r1, r2 = 16, 12

    rng = np.random.default_rng(7)
    A = jnp.asarray(rng.integers(
        0, 1 << 32, (r1, n_shards, WORDS_PER_ROW), dtype=np.uint32))
    B = jnp.asarray(rng.integers(
        0, 1 << 32, (r2, n_shards, WORDS_PER_ROW), dtype=np.uint32))

    count = jax.jit(lambda a, b: bitplane.hi_lo(jnp.sum(
        jax.lax.population_count(a & b).astype(jnp.int32), axis=-1)))

    def recursive():
        # the pre-pairwise inner loop: one dispatch + one host sync per
        # (row_a, row_b) combination
        out = np.zeros((r1, r2), np.int64)
        for i in range(r1):
            for j in range(r2):
                hi, lo = count(A[i], B[j])
                out[i, j] = bitplane.combine_hi_lo(
                    np.asarray(hi), np.asarray(lo))
        return out

    def pairwise():
        return bitplane.pairwise_counts(A, B)

    got_r, got_p = recursive(), pairwise()  # warm/compile + check
    assert np.array_equal(got_r, got_p), "recursive/pairwise mismatch"

    def measure(fn):
        t0 = time.perf_counter()
        fn()
        return (time.perf_counter() - t0) * 1000

    rec_ms = measure(recursive)
    pw_ms = measure(pairwise)
    tile = bitplane.pairwise_tile(n_shards)
    pw_dispatches = -(-r1 // tile) * -(-r2 // tile)
    print(json.dumps({
        "metric": "groupby_pairwise_vs_recursive",
        "value": round(rec_ms / pw_ms, 3),
        "unit": "speedup_x",
        "extra": {
            "platform": device.platform,
            "device_kind": getattr(device, "device_kind", ""),
            "n_shards": n_shards, "r1": r1, "r2": r2,
            "recursive_ms": round(rec_ms, 2),
            "pairwise_ms": round(pw_ms, 2),
            "recursive_dispatches": r1 * r2,
            "pairwise_dispatches": pw_dispatches,
            "tile": tile,
        },
    }), flush=True)


if __name__ == "__main__":
    from pilosa_tpu.utils import device

    device.boot()
    if len(sys.argv) > 1 and sys.argv[1] == "bsi-pallas":
        bsi_pallas_vs_jnp()
    elif len(sys.argv) > 1 and sys.argv[1] == "groupby-pairwise":
        groupby_pairwise()
    else:
        main()

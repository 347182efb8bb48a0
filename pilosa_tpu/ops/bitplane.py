"""Device kernels over dense row planes.

A "plane" is one row of one shard: a dense bitset of SHARD_WIDTH bits packed
little-endian into uint32 words (shape [WORDS_PER_ROW]). A "stack" is a batch
of planes (shape [R, WORDS_PER_ROW]).

These kernels are the TPU-native equivalent of the reference's hand-optimized
roaring container kernels (reference: roaring/roaring.go:3121-5196 — per
container-type intersect/union/difference/xor/popcount). Where the reference
dispatches on container representation (array/bitmap/run), we keep everything
dense in HBM and let the VPU chew through whole planes; set algebra is
elementwise and popcounts reduce with `lax.population_count`.

All functions are jitted and shape-polymorphic only through retracing; shapes
are static per compilation, which is what XLA wants.
"""

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ..shardwidth import SHARD_WIDTH, WORD_BITS, WORDS_PER_ROW

__all__ = [
    "intersect",
    "union",
    "difference",
    "xor",
    "not_",
    "popcount",
    "popcount_rows",
    "count_intersect",
    "union_rows",
    "any_set",
    "shift",
    "plane_from_columns",
    "columns_from_plane",
    "topn_counts",
    "pairwise_counts",
    "pairwise_counts_hi_lo",
    "pairwise_tile",
    "hi_lo",
    "combine_hi_lo",
]


def hi_lo(per_shard_counts, axis=None):
    """Overflow-safe cross-shard reduce: per-shard popcounts fit int32
    (<= SHARD_WIDTH = 2^20 bits/shard) but totals can exceed 2^31 past 2048
    shards, and TPU JAX runs with x64 disabled — so reduce (count >> 16) and
    (count & 0xffff) separately and recombine on host with exact Python ints
    (combine_hi_lo). Safe to 2^15 shards (~34 trillion columns/node).

    This is THE one overflow-splitting contract; every cross-shard count
    reduce in the framework routes through this pair of helpers."""
    return (jnp.sum(per_shard_counts >> 16, axis=axis),
            jnp.sum(per_shard_counts & 0xFFFF, axis=axis))


def combine_hi_lo(hi, lo):
    """Exact host total from a hi_lo() reduce pair (elementwise for array
    pairs, Python int for scalars)."""
    if np.ndim(hi):
        return (np.asarray(hi).astype(np.int64) << 16) + np.asarray(lo)
    return (int(hi) << 16) + int(lo)


@jax.jit
def intersect(a, b):
    return a & b


@jax.jit
def union(a, b):
    return a | b


@jax.jit
def difference(a, b):
    return a & ~b


@jax.jit
def xor(a, b):
    return a ^ b


@jax.jit
def not_(a):
    """Complement within the shard universe (used with an existence mask by
    the executor — reference: executor.go executeNot via index._exists)."""
    return ~a


@jax.jit
def popcount(a):
    """Number of set bits in a plane. int32 is safe: a plane holds at most
    SHARD_WIDTH (2^20) bits (reference popcount kernels: roaring.go:5291)."""
    return jnp.sum(jax.lax.population_count(a).astype(jnp.int32))


@jax.jit
def popcount_rows(stack):
    """Per-row popcount over a stack [R, W] -> [R] int32."""
    return jnp.sum(jax.lax.population_count(stack).astype(jnp.int32), axis=-1)


@jax.jit
def count_intersect(a, b):
    """Fused intersection-count — the north-star hot loop (reference:
    intersectionCount* kernels roaring.go:3121-3480). XLA fuses the AND into
    the popcount reduce; no intermediate plane is materialized."""
    return jnp.sum(jax.lax.population_count(a & b).astype(jnp.int32))


@jax.jit
def union_rows(stack):
    """OR-reduce a stack [R, W] -> [W] (used by ClearRow/Store fan-ins and
    time-quantum view unions, reference: view union paths)."""
    return jax.lax.reduce(
        stack,
        jnp.uint32(0),
        jax.lax.bitwise_or,
        dimensions=[0],
    )


@jax.jit
def any_set(a):
    """True iff any bit is set (reference: Row.Any / Bitmap.Any)."""
    return jnp.any(a != 0)


@partial(jax.jit, static_argnames=("n",))
def _shift_static(a, n):
    """Shift the whole plane toward higher column ids by n bits (reference:
    Row.Shift row.go:241, roaring shiftArray/shiftBitmap). Bits shifted past
    the end of the shard are dropped (per-shard semantics; the executor
    carries them across segments)."""
    word_shift, bit_shift = divmod(n, WORD_BITS)
    if word_shift:
        a = jnp.roll(a, word_shift)
        a = a.at[:word_shift].set(0)
    if bit_shift:
        carry = jnp.roll(a >> jnp.uint32(WORD_BITS - bit_shift), 1).at[0].set(0)
        a = (a << jnp.uint32(bit_shift)) | carry
    return a


def shift(a, n=1):
    n = int(n)
    if n < 0:
        raise ValueError("shift supports non-negative n only (toward higher columns)")
    if n == 0:
        return a
    return _shift_static(a, n)


def plane_from_columns(cols):
    """Host helper: build a [WORDS_PER_ROW] uint32 plane from shard-relative
    column offsets (native scatter, used by import paths and tests). Offsets
    must already be shard-relative — a value >= SHARD_WIDTH means the caller
    forgot to subtract the shard base, so fail loudly rather than let the
    scatter primitive silently drop it."""
    from .. import native

    cols = np.asarray(cols, dtype=np.uint64)
    if cols.size and int(cols.max()) >= SHARD_WIDTH:
        raise ValueError(
            f"column offset {int(cols.max())} >= shard width {SHARD_WIDTH}")
    plane = np.zeros(WORDS_PER_ROW, dtype=np.uint32)
    native.scatter(cols, plane)
    return plane


def columns_from_plane(plane):
    """Host helper: shard-relative column offsets of set bits, sorted."""
    from .. import native

    return native.extract(np.asarray(plane, dtype=np.uint32))


@partial(jax.jit, static_argnames=("k",))
def _topn_counts_jnp(stack, filter_plane, k):
    counts = popcount_rows(stack & filter_plane[None, :])
    vals, idx = jax.lax.top_k(counts, k)
    return vals, idx


# Per-axis row budget for one pairwise tile ([tile, S, W] stack). Matches
# exec.stacked.CHUNK_BYTES so a tile stack never exceeds one row-chunk
# upload; the serving layer derives its tile from CHUNK_BYTES directly.
PAIRWISE_TILE_BYTES = 128 * 1024 * 1024


def pairwise_tile(n_shards):
    """Rows per pairwise tile axis under the PAIRWISE_TILE_BYTES budget."""
    return max(1, PAIRWISE_TILE_BYTES // (n_shards * WORDS_PER_ROW * 4))


@lru_cache(maxsize=4)
def _pairwise_hi_lo_fn(has_filt):
    """(A [R1,S,W], B [R2,S,W], filt [S,W]?) -> (hi [R1,R2], lo [R1,R2])
    cross-product intersect counts, reduced over shards with the hi_lo
    overflow split. The A axis folds through a lax.map so the broadcast
    intermediate stays [R2, S, W] (one B-stack's worth) instead of
    materializing the full [R1, R2, S, W] cross product."""

    @jax.jit
    def fn(a, b, *filt):
        bf = b & filt[0][None] if has_filt else b

        def per_a(a_row):
            pc = jax.lax.population_count(a_row[None] & bf).astype(jnp.int32)
            return jnp.sum(pc, axis=-1)          # [R2, S]

        per_shard = jax.lax.map(per_a, a)        # [R1, R2, S]
        return hi_lo(per_shard, axis=-1)

    return fn


def pairwise_counts_hi_lo(a, b, filt=None):
    """One-tile pairwise intersect-count matrix as a device (hi, lo) pair:
    counts[i, j] = Σ_{s,w} popcount(a[i] & b[j] & filt). a: [R1, S, W],
    b: [R2, S, W], filt: [S, W] or None. Dispatches to the Pallas backend
    under the same opt-in gate as the count kernels when the per-pair bit
    budget fits its plain-int32 accumulator and the inputs live on one
    device (pallas_call can't be GSPMD-partitioned)."""
    from . import pallas_kernels
    from ..parallel.sharded import _is_multi_device

    if a.shape[0] == 0 or b.shape[0] == 0:
        z = jnp.zeros((a.shape[0], b.shape[0]), jnp.int32)
        return z, z
    n_bits = a.shape[1] * a.shape[2] * 32
    if pallas_kernels.enabled() and n_bits < 2**31 \
            and not _is_multi_device(a) and not _is_multi_device(b):
        m = pallas_kernels.pairwise_counts_stack(a, b, filt)
        # totals < 2^31 by the gate, so the plain split satisfies the
        # combine_hi_lo contract total = (hi << 16) + lo exactly
        return m >> 16, m & 0xFFFF
    fn = _pairwise_hi_lo_fn(filt is not None)
    if filt is not None:
        return fn(jnp.asarray(a), jnp.asarray(b), jnp.asarray(filt))
    return fn(jnp.asarray(a), jnp.asarray(b))


def pairwise_counts(A, B, filt=None, tile=None):
    """Host [R1, R2] int64 matrix of pairwise intersect counts over row
    stacks A [R1, S, W] and B [R2, S, W] (filt [S, W] optional) — the
    GroupBy cross product as one tiled popcount matrix instead of R1·R2
    per-combination scans (reference: executor.go:1238 iterates fragment
    scans per group). Tiled over BOTH row axes so device memory stays
    bounded by ~2·PAIRWISE_TILE_BYTES regardless of R1·R2; each tile pair
    is one fused dispatch + one host sync."""
    R1, R2 = int(A.shape[0]), int(B.shape[0])
    out = np.zeros((R1, R2), dtype=np.int64)
    if R1 == 0 or R2 == 0:
        return out
    if tile is None:
        tile = pairwise_tile(int(A.shape[1]))
    dfilt = jnp.asarray(filt) if filt is not None else None
    for i in range(0, R1, tile):
        a = jnp.asarray(A[i:i + tile])
        for j in range(0, R2, tile):
            b = jnp.asarray(B[j:j + tile])
            hi, lo = pairwise_counts_hi_lo(a, b, dfilt)
            out[i:i + tile, j:j + tile] = combine_hi_lo(hi, lo)
    return out


def topn_counts(stack, filter_plane, k):
    """Per-row intersection counts then top-k (reference: fragment.top
    fragment.go:1570 + cache heap merge). Returns (counts [k], slots [k]).
    top_k returns real slot indices even for zero counts — callers MUST drop
    entries with count == 0 (the reference's top excludes empty rows).
    Dispatches to the Pallas backend under the same opt-in gate as
    QueryKernels.count_expr. An empty stack yields zero counts on either
    backend (top_k would reject k > 0 rows)."""
    from . import pallas_kernels

    if stack.shape[0] == 0:
        return jnp.zeros(k, jnp.int32), jnp.zeros(k, jnp.int32)
    if pallas_kernels.enabled():
        return pallas_kernels.topn_counts_stack(stack, filter_plane, k)
    return _topn_counts_jnp(stack, filter_plane, k)

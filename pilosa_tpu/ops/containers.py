"""Device-resident compressed plane containers.

Dense [S, W] uint32 plane stacks (ops/bitplane.py) make every Count scan
S * W * 4 bytes of HBM, so for a bandwidth-bound popcount bytes moved is
the wall (device share of HBM peak: not measured on this round's code).
The reference
never pays this: roaring picks array/bitmap/run representation per 64K
block by density (reference: roaring/roaring.go container types;
PAPER.md §2.1). This module is the device analogue — per-fragment
representation choice with kernels that count compressed blocks
directly, never materializing the dense plane:

  dense   — today's format: one [S, W] uint32 stack (the escape hatch;
            forced-dense serving is bit-identical by construction
            because it IS the legacy array).
  sparse  — block-sparse: only the non-empty BLOCK_WORDS-word blocks
            survive, as (block_ids [NB] int32 sorted, blocks [NB, BW]
            uint32). Ids linearize (shard, block) row-major; padding
            uses an out-of-range sentinel id with zero blocks, so
            scatters drop it and popcounts ignore it.
  rle     — run-length: sorted disjoint [start, end) bit intervals as
            (run_shard, run_start, run_end) int32 triples with
            shard-relative offsets (the device analogue of roaring run
            containers). Padding runs are (shard=-1, 0, 0): empty and
            matching no real shard.

Counting discipline: the dense path keeps the per-shard hi_lo split
(ops/bitplane.hi_lo). Compressed direct counts reduce to ONE int32
total and split it as (t >> 16, t & 0xffff) — exact under the
combine_hi_lo contract because (hi << 16) + lo == t for any t >= 0 that
fits int32, which the chooser guarantees by refusing to compress a
stack whose bit capacity S * SHARD_WIDTH reaches 2^31 (same gate as the
Pallas pairwise kernels).

The chooser is deterministic in the host data (measured density /
non-empty blocks / run count — no sampling, no feedback loop), so a
rebuild of unchanged data always re-picks the same representation
(chooser-stability contract). The per-fragment choice is recorded in a
module ledger keyed (index, field, view) that the cost model, /debug/hbm
compression ratios, and /debug/heat admission pricing all read.

Layering: this module owns representations + kernels; exec/stacked.py
owns the cached placement, the chooser call site, and the jitted
serving programs (it passes its _tree_eval in, so expression semantics
stay defined in exactly one place).
"""

import os
import threading

import numpy as np

from ..shardwidth import SHARD_WIDTH, WORDS_PER_ROW

__all__ = [
    "BLOCK_WORDS",
    "Container",
    "OVERLAY_MAX_TERMS",
    "analyze",
    "build",
    "dense_container",
    "configure",
    "repr_mode",
    "kind_of",
    "flatten",
    "flat_arity",
    "norm_csig",
    "unflatten",
    "count_program",
    "plane_program",
    "with_overlay",
    "overlay_rows",
    "container_to_dense",
    "fragment_estimate",
    "field_estimate",
    "fragment_ledger",
    "reset_ledger",
]

#: words per block-sparse block: 128 words = 4096 bits = one VPU-friendly
#: [8, 128]-shaped tile per block on device. W is always a multiple
#: (WORDS_PER_ROW = 2^(exp-5) >= 2^11 for the supported exponent range).
BLOCK_WORDS = 128

#: sentinel block id for sparse padding: out of range for any real
#: (shard, block) by the sparse eligibility gate, so `.at[ids].set(...,
#: mode="drop")` discards padding and searchsorted matches pad-to-pad
#: only (whose blocks are zero — count-neutral either way).
SPARSE_SENTINEL = 1 << 30

#: auto-chooser caps: rle only pays off when the run count is small, and
#: the pairwise intersect kernel is O(NA * NB) — keep both bounded.
MAX_RLE_RUNS = 4096
MAX_RLE_PAIR = 1 << 22

#: a compressed representation must at least halve the bytes before auto
#: picks it — hysteresis against flapping near break-even, and it keeps
#: the (cheap, fused) dense kernels for data that barely compresses.
COMPRESS_ADVANTAGE = 0.5

#: auto only compresses fragments whose dense stack is at least this
#: big. Below the floor the dense plane is cheap anyway, while the
#: compressed forms fragment the serving jit-key space — every (tree,
#: container-signature) pair is its own compiled program, so a host
#: full of small fragments pays far more in compiles and cache pressure
#: than it saves in HBM. The floor (default 4 MiB ≈ a 32-shard stack)
#: keeps auto inert at toy scale and targets the actual bandwidth wall;
#: forced sparse/rle ignore it (differential tests and capacity
#: experiments run at CPU scale), and ops can lower it with
#: PILOSA_TPU_COMPRESS_FLOOR.
AUTO_COMPRESS_FLOOR = int(os.environ.get(
    "PILOSA_TPU_COMPRESS_FLOOR", 4 << 20))

_ARITY = {"dense": 1, "sparse": 2, "rle": 3}
_MODES = ("auto", "dense", "sparse", "rle")

#: max pending-delta overlay terms a compressed container accumulates
#: before the ingest merge forces a full rebuild (repr re-chosen from
#: the measured density). Each term adds a (kind, S, T) program variant
#: to the jit-key space, so the cap bounds compile churn too.
OVERLAY_MAX_TERMS = 4

_MODE_LOCK = threading.Lock()
_MODE = os.environ.get("PILOSA_TPU_CONTAINER_REPR", "auto")
if _MODE not in _MODES:
    _MODE = "auto"


def configure(repr_mode=None):
    """Apply --container-repr (auto|dense|sparse|rle). `dense` is the
    bit-identical escape hatch; `sparse`/`rle` force a representation
    where eligible (int32-safety gates still win) — for differential
    tests and capacity experiments."""
    global _MODE
    if repr_mode is None:
        return
    if repr_mode not in _MODES:
        raise ValueError(
            f"container repr must be one of {'|'.join(_MODES)}: "
            f"{repr_mode!r}")
    with _MODE_LOCK:
        _MODE = repr_mode


def repr_mode():
    return _MODE


# ------------------------------------------------------------------ ledger
#
# Per-leaf representation ledger: what the chooser last picked for each
# built leaf — keyed (index, field, view[, leaf]) since different rows
# of one fragment pick independently. Read by exec/plan.py (compressed
# bytes_touched estimates for non-resident leaves), /debug/hbm
# (compression ratios), and utils/workload.py (admission candidates
# priced by compressed bytes). Writes happen at stack-build time only —
# never on the per-query hot path.

_LEDGER_LOCK = threading.Lock()
_LEDGER = {}


def _ledger_note(fragment, kind, nbytes, dense_bytes, density):
    if fragment is None:
        return
    entry = {
        "repr": kind,
        "bytes": int(nbytes),
        "dense_bytes": int(dense_bytes),
        "ratio": round(dense_bytes / nbytes, 3) if nbytes else 1.0,
        "density": round(float(density), 6),
    }
    with _LEDGER_LOCK:
        _LEDGER[tuple(fragment)] = entry


def fragment_estimate(index, field, view, leaf=None):
    """Build-ledger estimate for one leaf of an (index, field, view)
    fragment: the exact record when `leaf` (e.g. a row id) was built
    before, else the per-leaf mean over every leaf of the fragment with
    the most common repr (different rows of one fragment legitimately
    pick different representations). None when never built."""
    with _LEDGER_LOCK:
        if leaf is not None:
            e = _LEDGER.get((index, field, view, leaf))
            if e is not None:
                return dict(e)
        entries = [e for k, e in _LEDGER.items()
                   if k[:3] == (index, field, view)]
    if not entries:
        return None
    n = len(entries)
    kinds = {}
    for e in entries:
        kinds[e["repr"]] = kinds.get(e["repr"], 0) + 1
    bytes_mean = sum(e["bytes"] for e in entries) // n
    dense_mean = sum(e["dense_bytes"] for e in entries) // n
    return {"repr": max(sorted(kinds), key=lambda k: kinds[k]),
            "bytes": bytes_mean,
            "dense_bytes": dense_mean,
            "ratio": round(dense_mean / bytes_mean, 3)
            if bytes_mean else 1.0,
            "density": round(
                sum(e["density"] for e in entries) / n, 6)}


def field_estimate(index, field):
    """Aggregate over every built leaf for the /debug/heat admission
    join (heat is summed at (index, field) there too — the sum prices
    re-admitting the field's whole built working set): {bytes,
    dense_bytes, ratio, reprs} or None."""
    total = dense = 0
    kinds = set()
    with _LEDGER_LOCK:
        for k, e in _LEDGER.items():
            if k[0] == index and k[1] == field:
                total += e["bytes"]
                dense += e["dense_bytes"]
                kinds.add(e["repr"])
    if not kinds:
        return None
    return {"bytes": total, "dense_bytes": dense,
            "ratio": round(dense / total, 3) if total else 1.0,
            "reprs": sorted(kinds)}


def fragment_ledger():
    """Snapshot for /debug surfaces: {"index/field/view": entry}."""
    with _LEDGER_LOCK:
        return {"/".join(map(str, k)): dict(e) for k, e in _LEDGER.items()}


def reset_ledger():
    with _LEDGER_LOCK:
        _LEDGER.clear()
        _REPR_OVERRIDES.clear()


# Per-(index, field) representation overrides from the adaptive layer's
# misestimate feedback: a fragment whose container_repr plan repeatedly
# reads MORE bytes than the dense scan it displaced gets forced dense at
# its next rebuild. Consulted in build() only under auto mode — forced
# --container-repr modes are the operator's word and win.
_REPR_OVERRIDES = {}  # (index, field) -> kind


def set_repr_override(index, field, kind):
    if kind not in _ARITY:
        raise ValueError(f"unknown container repr: {kind!r}")
    with _LEDGER_LOCK:
        _REPR_OVERRIDES[(index, field)] = kind


def repr_override(index, field):
    with _LEDGER_LOCK:
        return _REPR_OVERRIDES.get((index, field))


def repr_overrides():
    with _LEDGER_LOCK:
        return {f"{i}/{f}": k for (i, f), k in _REPR_OVERRIDES.items()}


# --------------------------------------------------------------- container


class Container:
    """One leaf fragment's device-resident plane stack in one of the
    three representations. `arrays` are the device buffers (arity by
    kind: dense 1, sparse 2, rle 3); `shape` is the logical dense
    [S, W]; `nbytes` the device bytes actually held (what the HBM
    ledger charges); `meta` the chooser's analysis (dense_bytes,
    density, ratio) for /debug/hbm.

    `overlay` counts pending-delta overlay terms parked after the base
    arrays by the streaming ingest merge (exec/ingest.py): each term is
    an (idx [K] int32, planes [K, W] uint32) pair of full replacement
    row planes, applied in append order after densifying — so a
    compressed fragment absorbs write churn without decaying to dense
    between merges. Dense containers never carry one (their writes
    scatter in place)."""

    __slots__ = ("kind", "shape", "arrays", "nbytes", "meta", "overlay")

    def __init__(self, kind, shape, arrays, nbytes, meta=None, overlay=0):
        self.kind = kind
        self.shape = tuple(shape)
        self.arrays = tuple(arrays)
        self.nbytes = int(nbytes)
        self.meta = meta or {}
        self.overlay = int(overlay)

    @property
    def csig(self):
        """Static program signature: enough for the jitted serving
        program to reconstruct the container from flat args (shapes are
        left to retracing, exactly like the dense fn cache). Dense is
        ("dense",) with no logical size — the program reads it off the
        array — so dense containers share fn-cache keys with the legacy
        raw-arity call sites; compressed kinds carry S because their
        component shapes don't determine it, plus the overlay term count
        when deltas are parked (a different flat arity is a different
        program)."""
        if self.kind == "dense":
            return ("dense",)
        if self.overlay:
            return (self.kind, self.shape[0], self.overlay)
        return (self.kind, self.shape[0])

    @property
    def gsig(self):
        """Program-cache grouping signature (exec/fusion.py): kind +
        exact component shapes, because a compiled program takes one
        shape per component."""
        return (self.kind, self.shape[0],
                tuple(tuple(a.shape) for a in self.arrays))


def kind_of(arrays):
    """Representation of a cached pool entry: rows/BSI pools hold raw
    dense device arrays (never Containers)."""
    return arrays.kind if isinstance(arrays, Container) else "dense"


def dense_container(stack):
    """Wrap an existing [S, W] device stack (bsi-condition masks,
    time-union folds, legacy paths) without copying."""
    nbytes = int(stack.size) * 4
    return Container("dense", stack.shape, (stack,), nbytes,
                     {"dense_bytes": nbytes, "ratio": 1.0})


def flatten(containers):
    """Device-arg flattening for the jitted serving programs."""
    return [a for c in containers for a in c.arrays]


def flat_arity(csig):
    return sum(_ARITY[entry[0]]
               + 2 * (entry[2] if len(entry) > 2 else 0)
               for entry in csig)


def norm_csig(csig):
    """Container signature from a legacy arity int (N all-dense raw
    arrays — exec/stacked's pre-container call sites and tests) or an
    already-proper tuple."""
    if isinstance(csig, int):
        return (("dense",),) * csig
    return tuple(csig)


def unflatten(csig, flat):
    """Inverse of flatten inside a traced program: [(kind, arrays, S)],
    or [(kind, arrays, S, ((oidx, oplanes), ...))] for entries whose
    csig carries overlay terms (the 3-tuple shape is preserved for
    overlay-free entries — existing programs and tests index [0]/[2])."""
    out, i = [], 0
    for entry in csig:
        kind = entry[0]
        n = _ARITY[kind]
        cont = (kind, tuple(flat[i:i + n]),
                entry[1] if len(entry) > 1 else -1)
        i += n
        terms = entry[2] if len(entry) > 2 else 0
        if terms:
            ov = tuple((flat[i + 2 * t], flat[i + 2 * t + 1])
                       for t in range(terms))
            i += 2 * terms
            cont = cont + (ov,)
        out.append(cont)
    return out


# ---------------------------------------------------------------- analysis

# 16-bit popcount table: exact host bit counts without unpacking the
# whole stack to booleans (the cold-build path analyzes every stack).
_POP16 = np.array([bin(i).count("1") for i in range(1 << 16)],
                  dtype=np.uint8)


def _host_popcount(stack):
    return int(_POP16[stack.view(np.uint16)].sum(dtype=np.int64))


def _shifted_left(stack):
    """bit i-1 of the plane at bit i's position (little-endian words,
    cross-word carry; column 0 sees 0)."""
    carry = np.concatenate(
        [np.zeros((stack.shape[0], 1), np.uint32), stack[:, :-1] >> 31],
        axis=1)
    return (stack << np.uint32(1)) | carry


def _pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def analyze(stack):
    """Host analysis of a [S, W] uint32 stack: exact bit count, density,
    non-empty block count, run count, and the projected device bytes of
    each representation (padded to the power-of-two component sizes the
    builders use)."""
    stack = np.ascontiguousarray(stack, dtype=np.uint32)
    s, w = stack.shape
    bits = _host_popcount(stack)
    bp = w // BLOCK_WORDS
    nonempty = int(stack.reshape(s, bp, BLOCK_WORDS).any(axis=2).sum())
    starts_mask = stack & ~_shifted_left(stack)
    runs = _host_popcount(starts_mask)
    nb_pad = _pow2(max(1, nonempty))
    nr_pad = _pow2(max(1, runs))
    return {
        "bits": bits,
        "density": bits / float(s * w * 32) if s and w else 0.0,
        "total_blocks": s * bp,
        "nonempty_blocks": nonempty,
        "runs": runs,
        "dense_bytes": s * w * 4,
        "sparse_bytes": nb_pad * (BLOCK_WORDS * 4 + 4),
        "rle_bytes": nr_pad * 12,
    }


def _sparse_eligible(s, w):
    # int32-exact totals AND sentinel strictly above every real id
    return (s * SHARD_WIDTH < 2**31
            and s * (w // BLOCK_WORDS) < SPARSE_SENTINEL)


def _rle_eligible(s, _w):
    # shard-relative [start, end] offsets go up to SHARD_WIDTH inclusive
    return s * SHARD_WIDTH < 2**31 and SHARD_WIDTH <= 2**30


def choose(info, s, w, mode=None):
    """Representation for a stack with this analysis under `mode`.
    Deterministic in (info, shape, mode) — the chooser-stability
    contract. Forced modes honor the int32-safety gates but skip the
    byte-advantage hysteresis."""
    mode = repr_mode() if mode is None else mode
    if mode == "dense":
        return "dense"
    if mode == "sparse":
        return "sparse" if _sparse_eligible(s, w) else "dense"
    if mode == "rle":
        return "rle" if _rle_eligible(s, w) else "dense"
    if info["dense_bytes"] < AUTO_COMPRESS_FLOOR:
        return "dense"
    budget = info["dense_bytes"] * COMPRESS_ADVANTAGE
    best, best_bytes = "dense", info["dense_bytes"]
    if (_sparse_eligible(s, w) and info["sparse_bytes"] <= budget
            and info["sparse_bytes"] < best_bytes):
        best, best_bytes = "sparse", info["sparse_bytes"]
    if (_rle_eligible(s, w) and info["runs"] <= MAX_RLE_RUNS
            and info["rle_bytes"] <= budget
            and info["rle_bytes"] < best_bytes):
        best, best_bytes = "rle", info["rle_bytes"]
    return best


# ------------------------------------------------------------ host builders


def _sparse_host(stack):
    """(block_ids [NBp] int32 sorted, blocks [NBp, BW] uint32), padded
    to a power of two with sentinel ids + zero blocks."""
    s, w = stack.shape
    bp = w // BLOCK_WORDS
    b3 = stack.reshape(s, bp, BLOCK_WORDS)
    ss, bb = np.nonzero(b3.any(axis=2))  # row-major: ids come out sorted
    ids = (ss.astype(np.int64) * bp + bb).astype(np.int32)
    n = len(ids)
    n_pad = _pow2(max(1, n))
    ids_p = np.full(n_pad, SPARSE_SENTINEL, dtype=np.int32)
    ids_p[:n] = ids
    blocks_p = np.zeros((n_pad, BLOCK_WORDS), dtype=np.uint32)
    blocks_p[:n] = b3[ss, bb]
    return ids_p, blocks_p


def _bit_positions(mask):
    """(shard_idx, bit_offset) of every set bit in a [S, W] mask, sorted
    by (shard, offset). Only the non-zero words are expanded — the masks
    this serves (run transitions) are sparse by construction."""
    ws, ww = np.nonzero(mask)
    if len(ws) == 0:
        return (np.empty(0, np.int32), np.empty(0, np.int32))
    bits = (mask[ws, ww][:, None] >> np.arange(32, dtype=np.uint32)) & 1
    rows, cols = np.nonzero(bits)
    return (ws[rows].astype(np.int32),
            (ww[rows] * 32 + cols).astype(np.int32))


def _rle_host(stack):
    """(run_shard, run_start, run_end) int32 triples of the maximal
    [start, end) set-bit runs per shard row, sorted by (shard, start)
    and padded to a power of two with empty (-1, 0, 0) runs."""
    s, w = stack.shape
    shifted = _shifted_left(stack)
    s_sh, s_pos = _bit_positions(stack & ~shifted)   # 0 -> 1 transitions
    e_sh, e_pos = _bit_positions(~stack & shifted)   # 1 -> 0 transitions
    # runs still open at the end of the shard close at SHARD_WIDTH
    tail = np.nonzero((stack[:, -1] >> np.uint32(31)) & 1)[0]
    if len(tail):
        e_sh = np.concatenate([e_sh, tail.astype(np.int32)])
        e_pos = np.concatenate(
            [e_pos, np.full(len(tail), w * 32, dtype=np.int32)])
        order = np.lexsort((e_pos, e_sh))
        e_sh, e_pos = e_sh[order], e_pos[order]
    if len(s_sh) != len(e_sh):  # pragma: no cover — structural invariant
        raise AssertionError("run transition mismatch")
    n = len(s_sh)
    n_pad = _pow2(max(1, n))
    run_shard = np.full(n_pad, -1, dtype=np.int32)
    run_start = np.zeros(n_pad, dtype=np.int32)
    run_end = np.zeros(n_pad, dtype=np.int32)
    run_shard[:n] = s_sh
    run_start[:n] = s_pos
    run_end[:n] = e_pos
    return run_shard, run_start, run_end


def build(host_stack, place_sharded, place_replicated, mode=None,
          fragment=None):
    """Analyze + choose + build + place one leaf stack.

    `place_sharded(arr)` places a dense [S, W] stack over the shard
    mesh (the legacy placement); `place_replicated(arr)` places a
    compressed component replicated — compressed arrays have no shard
    axis, and a replicated operand keeps the serving program a valid
    GSPMD launch next to mesh-sharded dense operands. Records the
    choice in the fragment ledger."""
    host_stack = np.ascontiguousarray(host_stack, dtype=np.uint32)
    s, w = host_stack.shape
    info = analyze(host_stack)
    kind = choose(info, s, w, mode)
    if ((mode or repr_mode()) == "auto" and fragment is not None
            and len(fragment) >= 2):
        override = repr_override(fragment[0], fragment[1])
        if override is not None:
            kind = override
    if kind == "sparse":
        ids, blocks = _sparse_host(host_stack)
        arrays = (place_replicated(ids), place_replicated(blocks))
        nbytes = int(ids.nbytes + blocks.nbytes)
    elif kind == "rle":
        arrays = tuple(place_replicated(a) for a in _rle_host(host_stack))
        nbytes = 3 * arrays[0].size * 4
    else:
        stack = place_sharded(host_stack)
        arrays = (stack,)
        nbytes = int(host_stack.nbytes)
    meta = {"dense_bytes": info["dense_bytes"],
            "density": round(info["density"], 6),
            "ratio": round(info["dense_bytes"] / nbytes, 3)
            if nbytes else 1.0}
    _ledger_note(fragment, kind, nbytes, info["dense_bytes"],
                 info["density"])
    return Container(kind, (s, w), arrays, nbytes, meta)


# ----------------------------------------------------------- traced kernels
#
# Everything below runs inside jitted serving programs (exec/stacked
# builds them) — jnp only, vmap-safe, int32 totals under the chooser's
# 2^31-bit gate.


def _split_total(t):
    """(hi, lo) of one int32 total, exact under combine_hi_lo."""
    return t >> 16, t & 0xFFFF


def _blocks_popcount_total(blocks):
    """Σ popcount over a [NB, BW] block stack (padding blocks are zero).
    Routes to the Pallas compressed-popcount kernel under the same
    opt-in gate as the dense count kernels."""
    import jax
    import jax.numpy as jnp

    from . import pallas_kernels

    if pallas_kernels.enabled():
        return pallas_kernels.count_blocks_stack(blocks)
    return jnp.sum(jax.lax.population_count(blocks).astype(jnp.int32))


def sparse_count_hi_lo(ids, blocks):  # noqa: ARG001 — ids fix the layout
    return _split_total(_blocks_popcount_total(blocks))


def sparse_intersect_blocks(ids_a, blocks_a, ids_b, blocks_b):
    """blocks_a ∩ blocks_b aligned onto a's block index: for each a
    block, binary-search b's sorted ids; unmatched blocks intersect to
    zero. Padding self-matches (sentinel == sentinel) but both sides'
    padding blocks are zero, so the result stays count-exact."""
    import jax.numpy as jnp

    pos = jnp.searchsorted(ids_b, ids_a)
    pos = jnp.clip(pos, 0, ids_b.shape[0] - 1)
    match = ids_b[pos] == ids_a
    return jnp.where(match[:, None], blocks_a & blocks_b[pos],
                     jnp.uint32(0))


def rle_count_hi_lo(run_shard, run_start, run_end):  # noqa: ARG001
    import jax.numpy as jnp

    return _split_total(jnp.sum(run_end - run_start))


def rle_intersect_hi_lo(a_sh, a_st, a_en, b_sh, b_st, b_en):
    """Pairwise [NA, NB] interval-overlap count restricted to matching
    shards; runs are disjoint within a container so the overlaps sum
    exactly. Padding runs (shard -1, empty) overlap nothing — even each
    other, because clip(0 - 0, 0) = 0."""
    import jax.numpy as jnp

    ov = jnp.clip(
        jnp.minimum(a_en[:, None], b_en[None, :])
        - jnp.maximum(a_st[:, None], b_st[None, :]), 0)
    same = a_sh[:, None] == b_sh[None, :]
    return _split_total(jnp.sum(jnp.where(same, ov, 0)))


def sparse_to_dense(ids, blocks, s, w):
    """Exact dense [S, W] stack from sparse blocks (scatter; sentinel
    padding ids drop)."""
    import jax.numpy as jnp

    nb = (s * w) // BLOCK_WORDS
    flat = jnp.zeros((nb, BLOCK_WORDS), jnp.uint32)
    flat = flat.at[ids].set(blocks, mode="drop")
    return flat.reshape(s, w)


def rle_to_dense(run_shard, run_start, run_end, s, w):
    """Exact dense [S, W] stack from runs: per shard, scatter +1/-1 run
    deltas over the bit axis, prefix-sum to coverage, pack 32 bits per
    word. lax.map keeps peak memory at one shard's bit vector instead
    of [S, SHARD_WIDTH] at once."""
    import jax
    import jax.numpy as jnp

    nbits = w * 32
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def per_shard(shard):
        m = (run_shard == shard).astype(jnp.int32)
        delta = jnp.zeros(nbits + 1, jnp.int32)
        delta = delta.at[run_start].add(m).at[run_end].add(-m)
        bits = jnp.cumsum(delta[:-1]) > 0
        return jnp.sum(
            jnp.where(bits.reshape(w, 32), weights[None, :],
                      jnp.uint32(0)),
            axis=1, dtype=jnp.uint32)

    return jax.lax.map(per_shard, jnp.arange(s, dtype=jnp.int32))


def _has_overlay(cont):
    return len(cont) > 3 and cont[3]


def to_dense(cont):
    """Dense [S, W] view of an unflattened (kind, arrays, S) container —
    identity for dense (forced-dense programs ARE the legacy ones).
    Pending-delta overlay terms scatter in append order after the base
    densifies: each term's planes are full replacements gathered from
    the authoritative host fragment, so later terms override earlier."""
    kind, arrays, s = cont[0], cont[1], cont[2]
    if kind == "dense":
        dense = arrays[0]
    elif kind == "sparse":
        dense = sparse_to_dense(arrays[0], arrays[1], s, WORDS_PER_ROW)
    else:
        dense = rle_to_dense(arrays[0], arrays[1], arrays[2], s,
                             WORDS_PER_ROW)
    if _has_overlay(cont):
        for oidx, oplanes in cont[3]:
            dense = dense.at[oidx].set(oplanes)
    return dense


def _count_container(cont):
    import jax
    import jax.numpy as jnp

    from . import bitplane

    kind, arrays = cont[0], cont[1]
    if not _has_overlay(cont):
        if kind == "sparse":
            return sparse_count_hi_lo(*arrays)
        if kind == "rle":
            return rle_count_hi_lo(*arrays)
    # overlay terms replace whole planes, so compressed direct counts
    # can't subtract what they cover — densify (exact) and count dense
    acc = to_dense(cont)
    per_shard = jnp.sum(
        jax.lax.population_count(acc).astype(jnp.int32), axis=-1)
    return bitplane.hi_lo(per_shard)


def _pure_intersect_leaves(sig):
    """Leaf slots of an all-& tree, or None for any other shape."""
    if sig[0] == "leaf":
        return [sig[1]]
    op, subs = sig
    if op != "&":
        return None
    out = []
    for sub in subs:
        r = _pure_intersect_leaves(sub)
        if r is None:
            return None
        out.extend(r)
    return out


def count_program(sig, csig, flat, tree_eval):
    """(hi, lo) count of one tree over flattened container args — THE
    compressed counting strategy, traced inside exec/stacked's jitted
    serving programs:

    1. single compressed leaf        -> direct compressed popcount
    2. pure-& tree, all-sparse       -> block-aligned intersect chain,
                                        counted without densifying
    3. pure-& pair of small rle      -> pairwise interval overlap
    4. anything else                 -> decompress leaves in-program
                                        (exact), legacy dense tree eval

    All four produce the same exact total; the choice is purely a
    bytes/FLOPs trade. `tree_eval` is StackedEvaluator._tree_eval —
    expression semantics live there, once."""
    import jax
    import jax.numpy as jnp

    from . import bitplane, pallas_kernels

    conts = unflatten(csig, flat)
    if sig[0] == "leaf":
        return _count_container(conts[sig[1]])
    leaf_ids = _pure_intersect_leaves(sig)
    if (leaf_ids is not None and len(leaf_ids) >= 2
            and not any(_has_overlay(conts[i]) for i in leaf_ids)):
        kinds = {conts[i][0] for i in leaf_ids}
        if kinds == {"sparse"}:
            first = conts[leaf_ids[0]]
            acc_ids, acc_blocks = first[1]
            for i in leaf_ids[1:]:
                ids_b, blocks_b = conts[i][1]
                if (len(leaf_ids) == 2 and pallas_kernels.enabled()):
                    # two-operand fast path: fuse the aligned AND into
                    # the Pallas popcount (one compressed HBM pass)
                    pos = jnp.searchsorted(ids_b, acc_ids)
                    pos = jnp.clip(pos, 0, ids_b.shape[0] - 1)
                    match = ids_b[pos] == acc_ids
                    other = jnp.where(match[:, None], blocks_b[pos],
                                      jnp.uint32(0))
                    return _split_total(
                        pallas_kernels.count_and_blocks_stack(
                            acc_blocks, other))
                acc_blocks = sparse_intersect_blocks(
                    acc_ids, acc_blocks, ids_b, blocks_b)
            return _split_total(_blocks_popcount_total(acc_blocks))
        if kinds == {"rle"} and len(leaf_ids) == 2:
            a, b = conts[leaf_ids[0]], conts[leaf_ids[1]]
            if a[1][0].shape[0] * b[1][0].shape[0] <= MAX_RLE_PAIR:
                return rle_intersect_hi_lo(*a[1], *b[1])
    acc = tree_eval(sig, [to_dense(c) for c in conts])
    per_shard = jnp.sum(
        jax.lax.population_count(acc).astype(jnp.int32), axis=-1)
    return bitplane.hi_lo(per_shard)


def plane_program(sig, csig, flat, tree_eval):
    """Dense [S, W] materialization of one tree over flattened container
    args — filter stacks and Row results must come out as the exact
    legacy planes, so every leaf decompresses in-program first."""
    return tree_eval(sig, [to_dense(c) for c in unflatten(csig, flat)])


# -------------------------------------------------------- ingest overlay


def with_overlay(cont, place_replicated, oidx, oplanes):
    """New Container with one more pending-delta overlay term appended
    after `cont`'s arrays: `oidx` [K] stack-row indices (int32) and
    `oplanes` [K, W] full replacement planes (uint32), placed replicated
    like every compressed component. The base representation is
    untouched — this is how the interval merge folds writes into a
    sparse/rle fragment without decaying it to dense."""
    oidx = np.ascontiguousarray(oidx, dtype=np.int32)
    oplanes = np.ascontiguousarray(oplanes, dtype=np.uint32)
    arrays = cont.arrays + (place_replicated(oidx),
                            place_replicated(oplanes))
    nbytes = cont.nbytes + int(oidx.nbytes + oplanes.nbytes)
    return Container(cont.kind, cont.shape, arrays, nbytes,
                     dict(cont.meta), overlay=cont.overlay + 1)


def overlay_rows(cont):
    """Total stack rows covered by a Container's overlay terms (the
    merge's rebuild-threshold input; counts duplicates across terms)."""
    base = _ARITY[cont.kind]
    return sum(int(cont.arrays[base + 2 * t].shape[0])
               for t in range(cont.overlay))


def container_to_dense(cont):
    """Dense [S, W] of a Container OBJECT (overlay applied) — the
    eager-mode analogue of the traced to_dense for call sites that hold
    the Container itself (exec/stacked's read-path decay)."""
    base = cont.arrays[:_ARITY[cont.kind]]
    dense = to_dense((cont.kind, base, cont.shape[0]))
    for t in range(cont.overlay):
        oidx = cont.arrays[_ARITY[cont.kind] + 2 * t]
        oplanes = cont.arrays[_ARITY[cont.kind] + 2 * t + 1]
        dense = dense.at[oidx].set(oplanes)
    return dense

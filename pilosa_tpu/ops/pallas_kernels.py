"""Pallas TPU kernels for the hot query loops.

The jnp kernels in `bitplane.py` already let XLA fuse AND+popcount+reduce;
and the north-star scan — Count(Intersect(a, b)) over every shard of a
1B-column index (reference: intersectionCount* kernels
roaring/roaring.go:3121-3480 driven by executor.mapReduce
executor.go:2455) — is pure AND+popcount+reduce, a shape XLA fuses on its
own. Pallas-vs-jnp device time: not measured on this round's code. These
kernels therefore exist as an *alternative backend* — explicit HBM->VMEM
streaming with a lane-resident accumulator — selectable with
`PILOSA_TPU_PALLAS=1`, not the default (keep-or-delete is ROADMAP D4).

Dispatch contract: callers consult `enabled()` — the opt-in env var on a
TPU backend, nothing else. There is no probe: a kernel the Mosaic compiler
refuses RAISES at its call site instead of silently becoming the jnp path.
`chip_smoke.py`'s kernels phase compiles every public function here with
interpret=False on the chip at the serving shapes and compares it bit for
bit with jnp. On the (explicitly requested) CPU backend the kernels run
through the Pallas interpreter, which is what the differential tests use.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..shardwidth import WORDS_PER_ROW

__all__ = [
    "enabled",
    "count_intersect_stack",
    "count_expr_stack",
    "count_blocks_stack",
    "count_and_blocks_stack",
    "topn_counts_stack",
    "pairwise_counts_stack",
    "bsi_range_mask",
]

# Rows of the [S, W] stack processed per grid step. 16 sublanes x 32768
# words = 2 MiB/input block in VMEM. (32 rows fails to compile on v5 lite.)
_BLOCK_ROWS = 16


def _interpret():
    """Interpreter off a TPU. utils/device.boot makes the backend explicit
    (a TPU, or JAX_PLATFORMS=cpu on purpose), so this never papers over a
    chip that failed to initialise."""
    return jax.default_backend() != "tpu"


def enabled():
    """Use pallas for the serving hot path? Opt-in AND a TPU backend (on
    the CPU backend the kernels would run through the very slow
    interpreter). No capability probe: a compile error propagates."""
    return (os.environ.get("PILOSA_TPU_PALLAS", "0") == "1"
            and jax.default_backend() == "tpu")


def _pad_rows(x, block):
    s = x.shape[0]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


# ---------------------------------------------------------------------------
# Count(expr) over a shard stack
# ---------------------------------------------------------------------------

def _count_expr_kernel(ops, n_blocks):
    """Kernel: fold `ops` over the operand blocks, popcount, and accumulate
    into a lane-resident [8, 128] int32 scratch across grid steps (vector
    adds only — no scalar reduce until the final host-side sum)."""
    from jax.experimental import pallas as pl

    def kernel(*refs):
        from ..parallel.sharded import apply_op_chain

        out_ref, acc_ref = refs[-2], refs[-1]
        acc = apply_op_chain(
            refs[0][:], [r[:] for r in refs[1:-2]], ops)
        pc = jax.lax.population_count(acc).astype(jnp.int32)
        part = jnp.sum(
            pc.reshape(_BLOCK_ROWS, WORDS_PER_ROW // 128, 128), axis=1)
        part = jnp.sum(part.reshape(_BLOCK_ROWS // 8, 8, 128), axis=0)

        @pl.when(pl.program_id(0) == 0)
        def _init():
            acc_ref[:] = jnp.zeros((8, 128), jnp.int32)

        acc_ref[:] += part

        @pl.when(pl.program_id(0) == n_blocks - 1)
        def _flush():
            out_ref[:] = acc_ref[:]

    return kernel


@functools.lru_cache(maxsize=64)
def _count_expr_call(ops, n_rows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    arity = len(ops) + 1
    n_blocks = n_rows // _BLOCK_ROWS
    spec = pl.BlockSpec((_BLOCK_ROWS, WORDS_PER_ROW), lambda i: (i, 0))
    # Every operand block is double-buffered and the fold + popcount
    # temporaries take about three blocks more (a v5e asked for 17.79 MiB
    # at three operands, chip_smoke PR 21). The default scoped limit of
    # 16 MiB only holds two operands, so ask for what the arity needs.
    block_bytes = _BLOCK_ROWS * WORDS_PER_ROW * 4
    vmem_limit = (2 * arity + 4) * block_bytes

    call = pl.pallas_call(
        _count_expr_kernel(ops, n_blocks),
        grid=(n_blocks,),
        in_specs=[spec] * arity,
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
    )

    @jax.jit
    def run(*planes):
        return jnp.sum(call(*planes))

    return run


def count_expr_stack(first, rest, ops):
    """sum(popcount(fold(ops, first, rest))) over a [S, W] uint32 stack.

    `ops` is a chain like ("&", "-") applied left-to-right (the kernel folds
    it with parallel.sharded.apply_op_chain — ONE definition of expression
    semantics, validated there). Zero-padding rows is safe: padding
    contributes popcount(0 op 0) = 0 for every op chain whose first operand
    is 0 — true for &, |, ^, and &~.
    """
    ops = tuple(ops)
    if len(ops) != len(rest):
        raise ValueError(
            f"op chain length {len(ops)} != operand count {len(rest)}")
    if first.shape[0] == 0:
        return jnp.int32(0)  # empty grid would never write the output
    planes = [_pad_rows(jnp.asarray(p), _BLOCK_ROWS)
              for p in (first, *rest)]
    run = _count_expr_call(ops, planes[0].shape[0], _interpret())
    return run(*planes)


def count_intersect_stack(a, b):
    """Fused Count(Intersect(a, b)) over shard stacks — the north star."""
    return count_expr_stack(a, [b], ("&",))


# ---------------------------------------------------------------------------
# Compressed-container block popcounts (ops/containers.py block-sparse repr)
# ---------------------------------------------------------------------------
#
# A block-sparse container stores only the non-empty BLOCK_WORDS=128-word
# blocks of a plane stack as [NB, 128] uint32 — already the native TPU
# tile shape, so each grid step streams 8 blocks from HBM and accumulates
# their popcounts into the same lane-resident [8, 128] int32 tile the
# count kernels use. The fused AND variant counts a two-operand sparse
# intersect chain in one compressed pass (the caller aligns operand B
# onto A's block index first; unmatched blocks arrive zeroed).
#
# PERF STATUS: correctness is covered by the containers differential
# suite (interpreter mode on CPU); device time on a real chip is
# UNMEASURED — like every kernel here these stay opt-in
# (PILOSA_TPU_PALLAS=1) and the jnp popcount path is the default.
# Int32 accumulation is safe under the chooser's gate (a container is
# only built compressed when its stack holds < 2^31 bits).

# Blocks per grid step: 8 sublanes x 128 lanes = one int32 tile.
_CB_BLOCK_ROWS = 8


def _count_blocks_kernel(n_steps, fuse_and):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        out_ref, acc_ref = refs[-2], refs[-1]
        x = refs[0][:] & refs[1][:] if fuse_and else refs[0][:]
        pc = jax.lax.population_count(x).astype(jnp.int32)

        @pl.when(pl.program_id(0) == 0)
        def _init():
            acc_ref[:] = jnp.zeros((_CB_BLOCK_ROWS, 128), jnp.int32)

        acc_ref[:] += pc

        @pl.when(pl.program_id(0) == n_steps - 1)
        def _flush():
            out_ref[:] = acc_ref[:]

    return kernel


@functools.lru_cache(maxsize=32)
def _count_blocks_call(n_rows, fuse_and, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_steps = n_rows // _CB_BLOCK_ROWS
    spec = pl.BlockSpec((_CB_BLOCK_ROWS, 128), lambda i: (i, 0))
    call = pl.pallas_call(
        _count_blocks_kernel(n_steps, fuse_and),
        grid=(n_steps,),
        in_specs=[spec] * (2 if fuse_and else 1),
        out_specs=pl.BlockSpec((_CB_BLOCK_ROWS, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_CB_BLOCK_ROWS, 128), jnp.int32),
        scratch_shapes=[pltpu.VMEM((_CB_BLOCK_ROWS, 128), jnp.int32)],
        interpret=interpret,
    )

    @jax.jit
    def run(*blocks):
        return jnp.sum(call(*blocks))

    return run


def count_blocks_stack(blocks):
    """Σ popcount over a [NB, 128] uint32 block stack (zero-padding rows
    count zero). Traced inside the compressed serving programs."""
    if blocks.shape[0] == 0:
        return jnp.int32(0)
    blocks = _pad_rows(jnp.asarray(blocks), _CB_BLOCK_ROWS)
    run = _count_blocks_call(blocks.shape[0], False, _interpret())
    return run(blocks)


def count_and_blocks_stack(a, b):
    """Σ popcount(a & b) over block-aligned [NB, 128] stacks — the fused
    compressed intersect-count (operands pre-aligned by the caller)."""
    if a.shape[0] == 0:
        return jnp.int32(0)
    a = _pad_rows(jnp.asarray(a), _CB_BLOCK_ROWS)
    b = _pad_rows(jnp.asarray(b), _CB_BLOCK_ROWS)
    run = _count_blocks_call(a.shape[0], True, _interpret())
    return run(a, b)


# ---------------------------------------------------------------------------
# TopN: per-row filtered popcounts
# ---------------------------------------------------------------------------

def _topn_kernel(r_blk):
    from jax.experimental import pallas as pl  # noqa: F401

    def kernel(rows_ref, filt_ref, out_ref):
        # rows_ref: [r_blk, W]; filt_ref: [1, W]; out_ref: [r_blk, 128].
        # Counts broadcast across a 128-lane minor dim to satisfy TPU tiling;
        # the caller reads lane 0.
        masked = rows_ref[:] & filt_ref[:]
        sums = jnp.sum(
            jax.lax.population_count(masked).astype(jnp.int32), axis=-1)
        out_ref[:] = jnp.broadcast_to(sums[:, None], (r_blk, 128))

    return kernel


@functools.lru_cache(maxsize=16)
def _topn_call(n_rows, interpret):
    from jax.experimental import pallas as pl

    grid = (n_rows // _BLOCK_ROWS,)
    call = pl.pallas_call(
        _topn_kernel(_BLOCK_ROWS),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, WORDS_PER_ROW), lambda i: (i, 0)),
            pl.BlockSpec((1, WORDS_PER_ROW), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, 128), jnp.int32),
        interpret=interpret,
    )

    @jax.jit
    def run(rows, filt):
        return call(rows, filt)[:, 0]

    return run


# ---------------------------------------------------------------------------
# Pairwise intersect-count matrix (GroupBy cross product)
# ---------------------------------------------------------------------------
#
# counts[i, j] = Σ_w popcount(A[i] & B[j] & filt) — matmul loop structure
# with popcount+add in place of multiply+add: the grid walks (A block,
# B block, word block) with the word axis innermost, the [8, 128] count
# tile accumulates in place across word blocks, and each step streams one
# B row block against the A block while the output tile stays resident.

# A rows per block (sublanes of the output tile).
_PW_A_BLOCK = 8
# B rows per block (lanes of the output tile).
_PW_B_BLOCK = 128
# Words per grid step: B block 128 x 4096 x 4 B = 2 MiB in VMEM; the
# flattened [R, S*W] word axis is always a multiple (W = 32768).
_PW_BLOCK_WORDS = 4096


def _pairwise_kernel(has_filt):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        if has_filt:
            a_ref, b_ref, filt_ref, out_ref = refs
            a = a_ref[:] & filt_ref[:]
        else:
            a_ref, b_ref, out_ref = refs
            a = a_ref[:]
        b = b_ref[:]
        # Unrolled over the (static, small) A block: each step is a
        # [B_BLOCK, W_BLOCK] AND+popcount reduced to one output row.
        rows = []
        for i in range(_PW_A_BLOCK):
            pc = jax.lax.population_count(a[i][None, :] & b)
            rows.append(jnp.sum(pc.astype(jnp.int32), axis=-1))
        part = jnp.stack(rows)                   # [A_BLOCK, B_BLOCK]

        @pl.when(pl.program_id(2) == 0)
        def _init():
            out_ref[:] = jnp.zeros((_PW_A_BLOCK, _PW_B_BLOCK), jnp.int32)

        out_ref[:] += part

    return kernel


@functools.lru_cache(maxsize=32)
def _pairwise_call(n_r1, n_r2, n_words, has_filt, interpret):
    from jax.experimental import pallas as pl

    grid = (n_r1 // _PW_A_BLOCK, n_r2 // _PW_B_BLOCK,
            n_words // _PW_BLOCK_WORDS)
    in_specs = [
        pl.BlockSpec((_PW_A_BLOCK, _PW_BLOCK_WORDS),
                     lambda i, j, w: (i, w)),
        pl.BlockSpec((_PW_B_BLOCK, _PW_BLOCK_WORDS),
                     lambda i, j, w: (j, w)),
    ]
    if has_filt:
        in_specs.append(
            pl.BlockSpec((1, _PW_BLOCK_WORDS), lambda i, j, w: (0, w)))
    call = pl.pallas_call(
        _pairwise_kernel(has_filt),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((_PW_A_BLOCK, _PW_B_BLOCK),
                               lambda i, j, w: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_r1, n_r2), jnp.int32),
        interpret=interpret,
    )
    return jax.jit(call)


def pairwise_counts_stack(a, b, filt=None):
    """[R1, R2] int32 pairwise intersect-count matrix over row stacks
    a [R1, S, W] and b [R2, S, W] (filt [S, W] optional). Plain int32
    accumulation — callers gate on S*SHARD_WIDTH < 2^31 set bits, exactly
    as QueryKernels.count_expr gates the count kernels. Zero padding rows
    contributes zero counts and is sliced off before returning."""
    r1, r2 = a.shape[0], b.shape[0]
    if r1 == 0 or r2 == 0:
        return jnp.zeros((r1, r2), jnp.int32)
    t = a.shape[1] * a.shape[2]
    a2 = _pad_rows(jnp.asarray(a).reshape(r1, t), _PW_A_BLOCK)
    b2 = _pad_rows(jnp.asarray(b).reshape(r2, t), _PW_B_BLOCK)
    run = _pairwise_call(a2.shape[0], b2.shape[0], t, filt is not None,
                         _interpret())
    if filt is not None:
        out = run(a2, b2, jnp.asarray(filt).reshape(1, t))
    else:
        out = run(a2, b2)
    return out[:r1, :r2]


# ---------------------------------------------------------------------------
# Fused BSI range compare (reference: rangeLTUnsigned fragment.go:1357-1400)
# ---------------------------------------------------------------------------
#
# The jnp path (ops/bsi.py) computes the (lt, eq, gt) comparator masks with
# a lax.scan, then combines with sign/exists in a second jitted call — XLA
# materializes the intermediate masks between the two programs. This kernel
# fuses the whole range op into ONE pass: each grid step streams a word
# block of all D magnitude planes + sign + exists from HBM once, unrolls
# the MSB-first comparator over the (static) depth with the predicate bits
# read from SMEM, applies the sign-magnitude combine for the (static)
# operator, and writes only the final row mask.
#
# PERF STATUS (honest, unlike a claimed win): correctness is verified
# against the jnp path by the differential suite (test_pallas.py,
# interpreter mode), but the fusion's device-time advantage is UNMEASURED —
# the count kernels above measured at parity with XLA's own fusion, and the
# same may hold here. Like them, this kernel stays opt-in
# (PILOSA_TPU_PALLAS=1), never the default. Measurement recipe (real chip):
#   time bsi_range_mask("lt", planes[D=16], sign, exists, pbits, False,
#   True) vs ops.bsi._range_lt_jnp on the same [16, WORDS_PER_ROW] inputs,
#   n>=30 dispatches, block_until_ready on the batch; record both ms here.

# Words per grid step of the BSI kernel. D+2 blocks of W_BLK words must fit
# VMEM with double buffering: 64 planes x 4 KiB x 4 B = 1 MiB per step.
_BSI_BLOCK_WORDS = 4096


def _bsi_range_kernel(op, allow_eq, neg_pred, depth):
    from jax.experimental import pallas as pl  # noqa: F401

    def kernel(pbits_ref, planes_ref, sign_ref, exists_ref, out_ref):
        _FULL = jnp.uint32(0xFFFFFFFF)  # built in-kernel: no captured consts
        w = planes_ref.shape[-1]
        eq = jnp.full((1, w), _FULL, dtype=jnp.uint32)
        lt = jnp.zeros((1, w), dtype=jnp.uint32)
        gt = jnp.zeros((1, w), dtype=jnp.uint32)
        # MSB-first unrolled comparator (zero-padded planes above the real
        # MSB carry pbit 0 and plane 0: an exact no-op on (lt, eq, gt)).
        for d in range(depth - 1, -1, -1):
            plane = planes_ref[d][None, :]
            pmask = jnp.where(pbits_ref[d] == 1, _FULL, jnp.uint32(0))
            gt = gt | (eq & plane & ~pmask)
            lt = lt | (eq & ~plane & pmask)
            eq = eq & ~(plane ^ pmask)
        sign = sign_ref[:]
        exists = exists_ref[:]
        pos = exists & ~sign
        neg = exists & sign
        eq_mask = _FULL if allow_eq else jnp.uint32(0)
        if op == "eq":
            base = neg if neg_pred else pos
            out = base & eq
        elif op == "lt":
            # (reference: rangeLT fragment.go:1335; ops/bsi.range_lt)
            if neg_pred:
                out = neg & (gt | (eq & eq_mask))
            else:
                out = neg | (pos & (lt | (eq & eq_mask)))
        else:  # gt (reference: rangeGT fragment.go:1403)
            if neg_pred:
                out = pos | (neg & (lt | (eq & eq_mask)))
            else:
                out = pos & (gt | (eq & eq_mask))
        out_ref[:] = out

    return kernel


@functools.lru_cache(maxsize=64)
def _bsi_range_call(op, allow_eq, neg_pred, depth, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = WORDS_PER_ROW // _BSI_BLOCK_WORDS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # pbits [depth] int32 in SMEM
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((depth, _BSI_BLOCK_WORDS), lambda i, _: (0, i)),
            pl.BlockSpec((1, _BSI_BLOCK_WORDS), lambda i, _: (0, i)),
            pl.BlockSpec((1, _BSI_BLOCK_WORDS), lambda i, _: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, _BSI_BLOCK_WORDS), lambda i, _: (0, i)),
    )
    call = pl.pallas_call(
        _bsi_range_kernel(op, allow_eq, neg_pred, depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, WORDS_PER_ROW), jnp.uint32),
        interpret=interpret,
    )

    @jax.jit
    def run(pbits, planes, sign, exists):
        return call(pbits, planes, sign[None, :], exists[None, :])[0]

    return run


def bsi_range_mask(op, planes, sign, exists, pbits, neg_pred, allow_eq):
    """Fused signed BSI range compare: one HBM pass over all planes.

    op: "eq" | "lt" | "gt" (NEQ composes as exists − eq at the caller,
    matching ops/bsi.py). planes: [D, W] magnitude bit planes (LSB first);
    sign/exists: [W]; pbits: [D] 0/1 predicate magnitude bits; neg_pred /
    allow_eq: static Python bools. Semantics are identical to
    ops.bsi.range_eq/range_lt/range_gt (differential-tested)."""
    planes = jnp.asarray(planes)
    depth = planes.shape[0]
    pbits = jnp.asarray(pbits, dtype=jnp.int32)
    # pad depth to a sublane multiple; zero planes with zero pbits are
    # comparator no-ops (see kernel comment)
    pad = (-depth) % 8
    if pad:
        planes = jnp.pad(planes, ((0, pad), (0, 0)))
        pbits = jnp.pad(pbits, (0, pad))
    run = _bsi_range_call(op, bool(allow_eq), bool(neg_pred),
                          int(planes.shape[0]), _interpret())
    return run(pbits, planes, jnp.asarray(sign), jnp.asarray(exists))


def topn_counts_stack(rows, filter_plane, k):
    """Per-row popcount(row & filter) then top_k — reference: fragment.top
    fragment.go:1570. rows: [R, W]; filter_plane: [W]. Returns (vals, idx),
    both [k]; callers drop zero-count entries (as bitplane.topn_counts)."""
    n = rows.shape[0]
    if n == 0:
        return jnp.zeros(k, jnp.int32), jnp.zeros(k, jnp.int32)
    rows = _pad_rows(jnp.asarray(rows), _BLOCK_ROWS)
    run = _topn_call(rows.shape[0], _interpret())
    counts = run(rows, jnp.asarray(filter_plane)[None, :])[:n]
    return jax.lax.top_k(counts, k)

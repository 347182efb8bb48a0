"""Differential tests: Pallas kernels vs the jnp kernels in ops/bitplane.

On CPU these run through the Pallas interpreter (same kernel bodies that
compile on TPU). Mirrors the reference's differential-test strategy of
checking optimized kernels against a naive implementation
(roaring/naive.go:29, roaring/fuzz_test.go).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from pilosa_tpu.ops import bitplane as bp  # noqa: E402
from pilosa_tpu.ops import pallas_kernels as pk  # noqa: E402
from pilosa_tpu.shardwidth import WORDS_PER_ROW  # noqa: E402


def _stack(rng, s):
    return rng.integers(0, 1 << 32, (s, WORDS_PER_ROW), dtype=np.uint32)


@pytest.mark.parametrize("s", [1, 5, 16, 33])
def test_count_intersect_matches_jnp(rng, s):
    a, b = _stack(rng, s), _stack(rng, s)
    want = int(np.sum(np.asarray(jax.lax.population_count(a & b))))
    assert int(pk.count_intersect_stack(a, b)) == want


@pytest.mark.parametrize("ops", [("&",), ("|",), ("^",), ("-",),
                                 ("&", "|"), ("|", "-", "^")])
def test_count_expr_matches_numpy(rng, ops):
    s = 7
    planes = [_stack(rng, s) for _ in range(len(ops) + 1)]
    acc = planes[0]
    for op, p in zip(ops, planes[1:]):
        if op == "&":
            acc = acc & p
        elif op == "|":
            acc = acc | p
        elif op == "^":
            acc = acc ^ p
        else:
            acc = acc & ~p
    want = int(np.sum(np.asarray(jax.lax.population_count(acc))))
    assert int(pk.count_expr_stack(planes[0], planes[1:], ops)) == want


def test_count_expr_zero_rows_pad_safe(rng):
    # padding rows are zero; every op chain must ignore them
    a = np.zeros((3, WORDS_PER_ROW), dtype=np.uint32)
    a[0, 0] = 0b1011
    b = np.full((3, WORDS_PER_ROW), 0xFFFFFFFF, dtype=np.uint32)
    assert int(pk.count_expr_stack(a, [b], ("&",))) == 3


@pytest.mark.parametrize("r", [4, 10, 16])
def test_topn_matches_bitplane(rng, r):
    rows = _stack(rng, r)
    filt = _stack(rng, 1)[0]
    k = min(r, 5)
    v1, i1 = pk.topn_counts_stack(rows, filt, k)
    v2, i2 = bp.topn_counts(rows, filt, k)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_enabled_respects_env(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "0")
    assert pk.enabled() is False


def _force_enabled(monkeypatch):
    """Simulate the serving gate being on (on CPU the real gate also
    requires backend == 'tpu', so force it for dispatch-wiring tests)."""
    monkeypatch.setattr(pk, "enabled", lambda: True)


def test_query_kernels_dispatch_enabled(rng, monkeypatch):
    """The QueryKernels hot path with the pallas gate ON must agree with
    the default jnp path (covers the dispatch wiring, not just the
    kernels)."""
    from pilosa_tpu.parallel.sharded import QueryKernels

    planes = [_stack(rng, 6) for _ in range(3)]
    want = int(QueryKernels.count_expr(planes, "&-"))
    _force_enabled(monkeypatch)
    assert int(QueryKernels.count_expr(planes, "&-")) == want


def test_topn_dispatch_enabled(rng, monkeypatch):
    rows, filt = _stack(rng, 9), _stack(rng, 1)[0]
    want_v, want_i = bp.topn_counts(rows, filt, 3)
    _force_enabled(monkeypatch)
    got_v, got_i = bp.topn_counts(rows, filt, 3)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_enabled_requires_tpu_backend(monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    if jax.default_backend() != "tpu":
        assert pk.enabled() is False


def test_opt_in_kernel_failure_propagates(rng, monkeypatch):
    """PILOSA_TPU_PALLAS=1 on a TPU backend is the whole gate — there is
    no capability probe, so a kernel the compiler refuses RAISES at its
    call site instead of silently becoming the jnp path."""
    from pilosa_tpu.parallel.sharded import QueryKernels

    assert not hasattr(pk, "available")
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")

    def refuse(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pk, "_count_expr_call", refuse)
    monkeypatch.setattr(pk, "_topn_call", refuse)
    assert pk.enabled() is True  # no trial kernel ran to decide this
    planes = [_stack(rng, 2) for _ in range(2)]
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        QueryKernels.count_expr(planes, "&")
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        bp.topn_counts(_stack(rng, 3), _stack(rng, 1)[0], 2)


def test_empty_stack_both_backends(monkeypatch):
    """Empty stacks: count is 0 and topn is all-zero on BOTH backends (the
    dispatcher guards before either backend sees the degenerate shape)."""
    from pilosa_tpu.shardwidth import WORDS_PER_ROW as W

    empty = np.zeros((0, W), dtype=np.uint32)
    filt = np.zeros(W, np.uint32)
    assert int(pk.count_expr_stack(empty, [empty], ("&",))) == 0
    v, i = pk.topn_counts_stack(empty, filt, 3)
    assert list(np.asarray(v)) == [0, 0, 0]
    v, i = bp.topn_counts(empty, filt, 3)  # jnp gate
    assert list(np.asarray(v)) == [0, 0, 0]
    _force_enabled(monkeypatch)
    v, i = bp.topn_counts(empty, filt, 3)  # pallas gate
    assert list(np.asarray(v)) == [0, 0, 0]


def test_query_kernels_dispatch_rejects_bad_op(rng, monkeypatch):
    from pilosa_tpu.parallel.sharded import QueryKernels

    _force_enabled(monkeypatch)
    planes = [_stack(rng, 2) for _ in range(2)]
    with pytest.raises(ValueError, match="unknown op"):
        QueryKernels.count_expr(planes, "+")


def test_query_kernels_dispatch_sharded_inputs(rng, monkeypatch):
    """Mesh-sharded stacks must take the jnp path (pallas_call can't be
    GSPMD-partitioned) and still produce the right count."""
    from pilosa_tpu.parallel.sharded import (
        QueryKernels, ShardedQueryEngine, _is_multi_device)

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device")
    engine = ShardedQueryEngine()
    s = engine.pad_shards(engine.n_devices)
    a, b = _stack(rng, s), _stack(rng, s)
    da, db = engine.place(a), engine.place(b)
    assert _is_multi_device(da)
    _force_enabled(monkeypatch)
    want = int(np.sum(np.asarray(jax.lax.population_count(a & b))))
    assert int(QueryKernels.count_expr([da, db], "&")) == want


# -------------------------------------------------- fused BSI range kernel


@pytest.mark.parametrize("op,allow_eq", [
    ("eq", False), ("lt", False), ("lt", True),
    ("gt", False), ("gt", True),
])
@pytest.mark.parametrize("neg_pred", [False, True])
def test_bsi_range_mask_matches_jnp(rng, op, allow_eq, neg_pred):
    """The fused pallas BSI comparator (one HBM pass) must be bit-identical
    to the ops.bsi jnp scan for every operator/sign combination
    (reference algorithm: rangeLTUnsigned fragment.go:1357-1400)."""
    from pilosa_tpu.ops import bsi
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    depth = 13
    planes = rng.integers(0, 1 << 32, (depth, WORDS_PER_ROW),
                          dtype=np.uint32)
    sign = rng.integers(0, 1 << 32, WORDS_PER_ROW, dtype=np.uint32)
    exists = rng.integers(0, 1 << 32, WORDS_PER_ROW, dtype=np.uint32)
    pred = int(rng.integers(0, 1 << depth))
    pbits = bsi.predicate_bits(pred, depth)

    if op == "eq":
        want = np.asarray(bsi._range_eq_jnp(
            planes, sign, exists, pbits, neg_pred))
        got = np.asarray(pk.bsi_range_mask(
            "eq", planes, sign, exists, pbits, neg_pred, False))
    elif op == "lt":
        want = np.asarray(bsi._range_lt_jnp(
            planes, sign, exists, pbits, neg_pred, allow_eq))
        got = np.asarray(pk.bsi_range_mask(
            "lt", planes, sign, exists, pbits, neg_pred, allow_eq))
    else:
        want = np.asarray(bsi._range_gt_jnp(
            planes, sign, exists, pbits, neg_pred, allow_eq))
        got = np.asarray(pk.bsi_range_mask(
            "gt", planes, sign, exists, pbits, neg_pred, allow_eq))
    assert np.array_equal(got, want), (op, allow_eq, neg_pred, pred)


def test_bsi_range_mask_depth_one_and_wide(rng):
    """Edge depths: 1 bit (heavy sublane padding) and 40 bits."""
    from pilosa_tpu.ops import bsi
    from pilosa_tpu.shardwidth import WORDS_PER_ROW

    for depth, pred in ((1, 1), (40, (1 << 37) + 12345)):
        planes = rng.integers(0, 1 << 32, (depth, WORDS_PER_ROW),
                              dtype=np.uint32)
        sign = np.zeros(WORDS_PER_ROW, dtype=np.uint32)
        exists = np.full(WORDS_PER_ROW, 0xFFFFFFFF, dtype=np.uint32)
        pbits = bsi.predicate_bits(pred, depth)
        want = np.asarray(bsi._range_lt_jnp(
            planes, sign, exists, pbits, False, True))
        got = np.asarray(pk.bsi_range_mask(
            "lt", planes, sign, exists, pbits, False, True))
        assert np.array_equal(got, want), depth


def test_bsi_executor_differential_under_pallas(tmp_path, monkeypatch, rng):
    """Full executor BSI conditions give identical results with the pallas
    backend forced on (interpret mode on CPU)."""
    monkeypatch.setenv("PILOSA_TPU_PALLAS", "1")
    monkeypatch.setattr(pk, "enabled", lambda: True)

    from pilosa_tpu.core import FieldOptions, Holder
    from pilosa_tpu.exec import Executor
    from pilosa_tpu.server.api import API

    holder = Holder(str(tmp_path)).open()
    api = API(holder)
    api.create_index("bp")
    api.create_field("bp", "v", FieldOptions.int_field(min=-300, max=300))
    f = holder.index("bp").field("v")
    cols = rng.choice(2_000_000, size=120, replace=False)
    vals = rng.integers(-300, 301, size=120)
    for c, v in zip(cols.tolist(), vals.tolist()):
        f.set_value(c, v)
    e = Executor(holder)

    def check(q, want_cols):
        got = sorted(int(c) for c in e.execute("bp", q)[0].columns())
        assert got == sorted(want_cols), q

    cv = dict(zip(cols.tolist(), vals.tolist()))
    check("Row(v > 50)", [c for c, v in cv.items() if v > 50])
    check("Row(v >= 50)", [c for c, v in cv.items() if v >= 50])
    check("Row(v < -100)", [c for c, v in cv.items() if v < -100])
    check("Row(v <= -100)", [c for c, v in cv.items() if v <= -100])
    check("Row(v == 0)", [c for c, v in cv.items() if v == 0])
    check("Row(v != 7)", [c for c, v in cv.items() if v != 7])
    holder.close()


# ------------------------------------------------------------- pairwise counts


def _pw_stacks(rng, r1, r2, s):
    """[R, S, W] row stacks with moderate density."""
    a = rng.integers(0, 1 << 32, (r1, s, WORDS_PER_ROW), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, (r2, s, WORDS_PER_ROW), dtype=np.uint32)
    return a, b


def _pw_naive(a, b, filt=None):
    out = np.zeros((a.shape[0], b.shape[0]), dtype=np.int64)
    for i in range(a.shape[0]):
        for j in range(b.shape[0]):
            m = a[i] & b[j]
            if filt is not None:
                m = m & filt
            out[i, j] = int(np.bitwise_count(m).sum())
    return out


@pytest.mark.parametrize("r1,r2,s", [(1, 1, 1), (3, 5, 2), (9, 4, 2)])
def test_pairwise_jnp_matches_naive(rng, r1, r2, s):
    a, b = _pw_stacks(rng, r1, r2, s)
    np.testing.assert_array_equal(bp.pairwise_counts(a, b), _pw_naive(a, b))


def test_pairwise_jnp_with_filter(rng):
    a, b = _pw_stacks(rng, 4, 3, 2)
    filt = rng.integers(0, 1 << 32, (2, WORDS_PER_ROW), dtype=np.uint32)
    np.testing.assert_array_equal(
        bp.pairwise_counts(a, b, filt), _pw_naive(a, b, filt))


def test_pairwise_empty_rows(rng):
    a, b = _pw_stacks(rng, 3, 2, 1)
    empty = np.zeros((0, 1, WORDS_PER_ROW), dtype=np.uint32)
    assert bp.pairwise_counts(empty, b).shape == (0, 2)
    assert bp.pairwise_counts(a, empty).shape == (3, 0)
    hi, lo = bp.pairwise_counts_hi_lo(empty, b)
    assert np.asarray(hi).shape == (0, 2)


def test_pairwise_tiled_matches_untiled(rng):
    # tile smaller than both axes: the host tiling must reassemble the
    # same matrix the one-shot kernel produces
    a, b = _pw_stacks(rng, 7, 6, 1)
    want = _pw_naive(a, b)
    np.testing.assert_array_equal(bp.pairwise_counts(a, b, tile=2), want)
    np.testing.assert_array_equal(bp.pairwise_counts(a, b, tile=3), want)


@pytest.mark.parametrize("r1,r2", [(1, 1), (8, 128), (9, 5)])
def test_pairwise_pallas_matches_naive(rng, monkeypatch, r1, r2):
    """Pallas pairwise kernel (interpreter on CPU) vs naive, covering
    exact block multiples and row padding on both axes."""
    _force_enabled(monkeypatch)
    a, b = _pw_stacks(rng, r1, r2, 1)
    got = np.asarray(pk.pairwise_counts_stack(a, b))
    np.testing.assert_array_equal(got, _pw_naive(a, b))


def test_pairwise_pallas_with_filter(rng, monkeypatch):
    _force_enabled(monkeypatch)
    a, b = _pw_stacks(rng, 3, 2, 1)
    filt = rng.integers(0, 1 << 32, (1, WORDS_PER_ROW), dtype=np.uint32)
    got = np.asarray(pk.pairwise_counts_stack(a, b, filt))
    np.testing.assert_array_equal(got, _pw_naive(a, b, filt))


def test_pairwise_dispatch_enabled_matches_jnp(rng, monkeypatch):
    """pairwise_counts_hi_lo with the pallas gate ON must agree with the
    jnp path AND satisfy the combine_hi_lo contract."""
    a, b = _pw_stacks(rng, 4, 3, 2)
    want = bp.combine_hi_lo(*bp.pairwise_counts_hi_lo(a, b))
    _force_enabled(monkeypatch)
    hi, lo = bp.pairwise_counts_hi_lo(a, b)
    np.testing.assert_array_equal(bp.combine_hi_lo(hi, lo), want)

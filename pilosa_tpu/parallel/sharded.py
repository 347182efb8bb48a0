"""Sharded query execution over a device mesh.

The reference's scale-out is one SPMD axis: columns are range-partitioned
into shards and every read fans per-shard map functions out over nodes,
tree-reducing results (reference: executor.mapReduce executor.go:2455,
cluster.shardNodes cluster.go:883). Here that axis maps onto a
`jax.sharding.Mesh` axis named "shards": row planes stack into [S, W]
arrays sharded across devices, per-shard set algebra is pure elementwise
work on the local slice, and the cross-shard reduce is an ICI collective
(psum) instead of the reference's HTTP merge.

Two layers:
- `QueryKernels`: jitted stacked-plane kernels (single device or sharded —
  the same code; XLA partitions it over whatever sharding the inputs carry).
- `ShardedQueryEngine`: owns a Mesh and the shard->device placement,
  uploads fragment rows into sharded stacks, and runs the kernels with
  shard_map so reduces ride ICI.
"""

from functools import partial

import numpy as np


def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def apply_op_chain(acc, planes, ops):
    """Fold an operator chain over aligned plane stacks — THE definition of
    expression semantics, shared by the single-device and mesh paths."""
    if len(ops) != len(planes):
        raise ValueError(
            f"op chain length {len(ops)} != operand count {len(planes)}")
    for op, p in zip(ops, planes):
        if op == "&":
            acc = acc & p
        elif op == "|":
            acc = acc | p
        elif op == "^":
            acc = acc ^ p
        elif op == "-":
            acc = acc & ~p
        else:
            raise ValueError(f"unknown op {op!r}")
    return acc


def build_global_mesh(axis="shards"):
    """1-D mesh over the GLOBAL device list, process-major: each
    process's addressable block is contiguous along the shard axis —
    exactly what `jax.make_array_from_process_local_data` fills. On a
    single process this is the same mesh ShardedQueryEngine builds; in
    multi-controller SPMD (cluster/spmd.py) every process constructs the
    identical mesh over the identical device order, the requirement for
    collective programs to line up."""
    jax, _ = _jax()

    devices = sorted(jax.devices(),
                     key=lambda d: (d.process_index, d.id))
    return jax.sharding.Mesh(np.array(devices), (axis,))


def _is_multi_device(x):
    """True when `x` is a jax array spanning more than one device."""
    sharding = getattr(x, "sharding", None)
    if sharding is None:
        return False
    try:
        return len(sharding.device_set) > 1
    except AttributeError:
        return False


_count_expr_cache = {}


def _hi_lo():
    """Canonical overflow-safe reduce helpers (ops.bitplane), imported
    lazily to preserve this module's jax-free import time."""
    from ..ops.bitplane import combine_hi_lo, hi_lo

    return hi_lo, combine_hi_lo


def _count_expr_fn(ops, arity):
    """Module-cached jitted fused expression-count kernel (one compile per
    (ops, arity), reused forever). Returns an (hi, lo) int32 pair."""
    jax, jnp = _jax()

    hi_lo, _ = _hi_lo()
    fn = _count_expr_cache.get((ops, arity))
    if fn is None:
        @jax.jit
        def fn(*planes):
            acc = apply_op_chain(planes[0], planes[1:], ops)
            per_shard = jnp.sum(
                jax.lax.population_count(acc).astype(jnp.int32), axis=-1)
            return hi_lo(per_shard)

        _count_expr_cache[(ops, arity)] = fn
    return fn


# ---------------------------------------------------------------------------
# Stacked kernels (work on [S, W] plane stacks; S = shards)
# ---------------------------------------------------------------------------

class QueryKernels:
    """Batched query kernels over stacked shard planes. Each kernel is ONE
    XLA computation for all shards — a single device dispatch (vs. the
    executor's per-shard chains), and the unit the mesh engine shard_maps.
    Kernels are module-cached; calls never retrace."""

    @staticmethod
    def count_intersect(a, b):
        """Σ_shards popcount(a & b) — the north-star query."""
        return QueryKernels.count_expr([a, b], "&")

    @staticmethod
    def count_expr(planes, ops):
        """Evaluate a fused op chain over aligned stacks then popcount.
        `planes`: list of [S, W] stacks; `ops`: string like "&|^" applied
        left-to-right. Dispatches to the Pallas backend when opted in
        (PILOSA_TPU_PALLAS=1) AND the inputs live on at most one device —
        pallas_call under plain jit can't be GSPMD-partitioned, so
        mesh-sharded stacks always take the jnp path (which XLA partitions
        over whatever sharding the inputs carry). The jnp path is also the
        default on a single device — measured at parity on TPU (see
        ops/pallas_kernels.py)."""
        from ..ops import pallas_kernels

        # Pallas accumulates a plain int32 total, so route stacks that
        # could exceed 2^31 set bits (>2048 full shards) to the hi/lo jnp
        # path — the pallas kernel has no hi/lo split yet.
        n_bits = planes[0].shape[0] * planes[0].shape[1] * 32
        if pallas_kernels.enabled() and n_bits < 2**31 and not any(
                _is_multi_device(p) for p in planes):
            return int(pallas_kernels.count_expr_stack(
                planes[0], planes[1:], tuple(ops)))
        return _hi_lo()[1](*_count_expr_fn(ops, len(planes))(*planes))


# ---------------------------------------------------------------------------
# Mesh engine
# ---------------------------------------------------------------------------

class ShardedQueryEngine:
    """Distributes stacked shard planes across a 1-D "shards" mesh and runs
    query steps with shard_map + psum (the ICI replacement for the
    reference's cross-node HTTP merge)."""

    def __init__(self, devices=None, axis="shards"):
        jax, jnp = _jax()

        self.devices = list(devices if devices is not None else jax.devices())
        self.axis = axis
        self.mesh = jax.sharding.Mesh(np.array(self.devices), (axis,))
        self._compiled = {}

    @property
    def n_devices(self):
        return len(self.devices)

    def sharding(self):
        import jax

        return jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.axis))

    def pad_shards(self, n_shards):
        """Shard count padded to a multiple of the mesh size (padding shards
        are all-zero planes and cannot affect set-algebra results)."""
        d = self.n_devices
        return ((n_shards + d - 1) // d) * d

    def place(self, stack):
        """Upload/reshard a [S, W] host stack across the mesh."""
        import jax

        return jax.device_put(stack, self.sharding())

    # -- query steps --------------------------------------------------------

    def count_intersect(self, a, b):
        """Distributed Intersect+Count: local popcount per device slice,
        psum across the shard axis over ICI."""
        jax, jnp = _jax()
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        hi_lo, combine = _hi_lo()
        key = ("count_intersect",)
        fn = self._compiled.get(key)
        if fn is None:
            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=(P(self.axis), P(self.axis)),
                     out_specs=(P(), P()))
            def fn(a, b):
                per_shard = jnp.sum(
                    jax.lax.population_count(a & b).astype(jnp.int32),
                    axis=-1)
                hi, lo = hi_lo(per_shard)
                return (jax.lax.psum(hi, self.axis),
                        jax.lax.psum(lo, self.axis))

            self._compiled[key] = fn
        return combine(*fn(a, b))

    def query_step(self, planes, ops):
        """Distributed fused expression count: planes is a list of [S, W]
        sharded stacks, ops the operator chain (see QueryKernels.count_expr).
        One jit per (ops, arity): elementwise chain on the local slice, one
        psum across ICI."""
        jax, jnp = _jax()
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        hi_lo, combine = _hi_lo()
        key = ("expr", ops, len(planes))
        fn = self._compiled.get(key)
        if fn is None:
            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=tuple(P(self.axis) for _ in planes),
                     out_specs=(P(), P()))
            def fn(*planes):
                acc = apply_op_chain(planes[0], planes[1:], ops)
                per_shard = jnp.sum(
                    jax.lax.population_count(acc).astype(jnp.int32),
                    axis=-1)
                hi, lo = hi_lo(per_shard)
                return (jax.lax.psum(hi, self.axis),
                        jax.lax.psum(lo, self.axis))

            self._compiled[key] = fn
        return combine(*fn(*planes))

    def topn_step(self, stack, filter_stack, k):
        """Distributed TopN over a [R, S, W] row×shard stack: per-device
        partial counts per row, psum over shards, then top_k — all inside
        one jitted program (reference analog: per-node TopN + heap merge,
        executor.go:930)."""
        jax, jnp = _jax()
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        hi_lo, combine = _hi_lo()
        key = ("topn",)
        fn = self._compiled.get(key)
        if fn is None:
            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=(P(None, self.axis), P(self.axis)),
                     out_specs=(P(), P()))
            def fn(stack, filt):
                per_shard = jnp.sum(
                    jax.lax.population_count(
                        stack & filt[None]).astype(jnp.int32),
                    axis=-1)                      # [R, S_local]
                hi, lo = hi_lo(per_shard, axis=-1)
                return (jax.lax.psum(hi, self.axis),
                        jax.lax.psum(lo, self.axis))

            self._compiled[key] = fn
        hi, lo = fn(stack, filter_stack)
        # Exact int64 totals on host, then top-k (device top_k would need
        # the combined counts in one register, which overflows int32 past
        # 2048 shards).
        totals = combine(hi, lo)
        order = np.lexsort((np.arange(len(totals)), -totals))[:k]
        return totals[order], order.astype(np.int32)

    def pairwise_step(self, a, b, filt=None):
        """Distributed pairwise intersect-count matrix (the GroupBy cross
        product): a [R1, S, W] and b [R2, S, W] row stacks sharded over the
        shard axis, optional filt [S, W]. Each device computes its local
        [R1, R2] partial matrix (folding the A axis through lax.map so the
        broadcast intermediate stays one B-stack wide), then the partials
        psum over ICI. Returns the host int64 [R1, R2] matrix."""
        jax, jnp = _jax()
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        hi_lo, combine = _hi_lo()
        has_filt = filt is not None
        key = ("pairwise", has_filt)
        fn = self._compiled.get(key)
        if fn is None:
            in_specs = (P(None, self.axis), P(None, self.axis)) + (
                (P(self.axis),) if has_filt else ())

            @jax.jit
            @partial(shard_map, mesh=self.mesh, in_specs=in_specs,
                     out_specs=(P(), P()))
            def fn(a, b, *filt):
                bf = b & filt[0][None] if has_filt else b

                def per_a(a_row):
                    pc = jax.lax.population_count(a_row[None] & bf)
                    return jnp.sum(pc.astype(jnp.int32), axis=-1)

                per_shard = jax.lax.map(per_a, a)    # [R1, R2, S_local]
                hi, lo = hi_lo(per_shard, axis=-1)
                return (jax.lax.psum(hi, self.axis),
                        jax.lax.psum(lo, self.axis))

            self._compiled[key] = fn
        args = (a, b, filt) if has_filt else (a, b)
        return combine(*fn(*args))

    def sum_step(self, planes, sign, exists, filt):
        """Distributed BSI Sum: per-plane popcounts psum'd over shards.
        planes [D, S, W]; sign/exists/filt [S, W]."""
        jax, jnp = _jax()
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        hi_lo, combine = _hi_lo()
        key = ("sum",)
        fn = self._compiled.get(key)
        if fn is None:
            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=(P(None, self.axis), P(self.axis),
                               P(self.axis), P(self.axis)),
                     out_specs=(P(), P(), P(), P(), P(), P()))
            def fn(planes, sign, exists, filt):
                consider = exists & filt
                pos = consider & ~sign
                neg = consider & sign
                pc = jnp.sum(jax.lax.population_count(
                    planes & pos[None]).astype(jnp.int32), axis=-1)
                nc = jnp.sum(jax.lax.population_count(
                    planes & neg[None]).astype(jnp.int32), axis=-1)
                cc = jnp.sum(jax.lax.population_count(
                    consider).astype(jnp.int32), axis=-1)
                p_hi, p_lo = hi_lo(pc, axis=-1)
                n_hi, n_lo = hi_lo(nc, axis=-1)
                c_hi, c_lo = hi_lo(cc)
                return tuple(jax.lax.psum(x, self.axis)
                             for x in (p_hi, p_lo, n_hi, n_lo, c_hi, c_lo))

            self._compiled[key] = fn
        p_hi, p_lo, n_hi, n_lo, c_hi, c_lo = [
            np.asarray(x) for x in fn(planes, sign, exists, filt)]
        total = 0
        for i in range(planes.shape[0]):
            total += combine(p_hi[i], p_lo[i]) << i
            total -= combine(n_hi[i], n_lo[i]) << i
        return total, combine(c_hi, c_lo)

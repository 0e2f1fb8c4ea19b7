"""Mega-kernel factor executor — O(1) compiled programs in matrix size.

The streamed executor (numeric/stream.py) bounded compile count by
distinct shape keys, but its keys still carry per-group axes — padded
batch, A-entry count, the child-set shape tuple — so the compiled-kernel
count grows with the matrix (BENCH_r02: 119 kernels for 455 groups at
n=110592, dead in `factor-compile` at the 1350 s watchdog without one
factor FLOP executed).  This executor closes the program set the way
fixed-function hardware closes it (one medium-granularity dataflow
engine serving every front shape, arXiv:2406.10511; one uniform kernel
amortized over many heterogeneous small systems, arXiv:1909.04539):

* the plan's shape-key CLOSURE pass (numeric/plan._close_shape_keys,
  ``SLU_TPU_BUCKET_CLOSED``/``SLU_TPU_BUCKET_KEYS``) maps every (W, U)
  dispatch key onto a small fixed set of canonical ladder rungs;
* per closed bucket, ONE jitted program whose per-group variability is
  DATA, not code: batch, A-entry and child-table axes are padded to the
  bucket's canonical rungs, the child extend-add runs as a ``lax.scan``
  over stacked per-set tables (factor.group_step's tuple branch — the
  same ``extend_add_set`` arithmetic the other executors unroll), and
  the Schur pool / pattern values are rung-padded so the program shapes
  do not encode exact matrix sizes;
* programs are AOT-staged (trace → lower → compile) at first use, so
  the compile census records the exact stage split and the persistent
  XLA cache (utils/jaxcache.py) serves the whole set from disk on any
  later run whose buckets are already resident — the cross-run warm
  start ``scripts/warm_compile_cache.py`` prebakes for a serving fleet.

Equivalence contract: padding is index-sentinel no-ops (OOB drops/zero
fills) and batch slots are identity fronts, so the factors are BITWISE
identical to the streamed and fused executors on the same plan
(tests/test_megakernel.py; the PR 5 schedule guarantee carries over
because closure runs before the schedule branch).  The PR 7 checkpoint
/ resume splice is preserved: frontiers store the UNPADDED pool, so a
mega checkpoint resumes under stream and vice versa.

Mesh runs: the per-bucket programs shard exactly like the streamed
kernels (stream._kernel) — batch-over-"snode", columns-over-"panel" on
the dense factor math, replicated index metadata, the Schur pool
replicated or 1-D partitioned via ``factor.pool_spec`` — so a mesh no
longer downgrades mega→stream: the closed program set and the GSPMD
sharding compose.  The bitwise guarantee above is a SINGLE-DEVICE
contract; under GSPMD the partitioner re-tiles the batched triangular
solves, which (like stream-under-mesh) perturbs low-order bits — mesh
runs carry the allclose-class contract instead, and the BITWISE mesh
tier is the shard_map executor (parallel/spmd.py), whose full-order
replay sidesteps the partitioner entirely
(tests/test_spmd.py exercises mega-under-mesh both ways).
"""

from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from superlu_dist_tpu.numeric.factor import group_step
from superlu_dist_tpu.numeric.plan import FactorPlan, bucket_rung
from superlu_dist_tpu.numeric.stream import StreamExecutor, _pad_to
from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
from superlu_dist_tpu.symbolic.symbfact import _front_flops

#: ladder growth for the pool / pattern-value rungs: these pad real HBM
#: (not index no-op space), so the rung is fine — <= 25% overhead buys
#: program shapes that don't encode exact matrix sizes (cross-matrix
#: cache hits for the fleet warm start)
_STORE_GROWTH = 1.25


@functools.lru_cache(maxsize=None)
def _mega_kernel(dims, la, child_dims, pool_len, avals_len, dtype, pivot,
                 gemm_prec="highest", mesh=None,
                 pool_partition=False):
    """ONE jitted program for a closed shape bucket.

    Everything per-group — which fronts, which A entries, which children
    — arrives as device-array arguments at canonical shapes; the program
    itself is pure dataflow.  `pivot`/`gemm_prec` are the
    caller-resolved SLU_TPU_PIVOT_KERNEL / SLU_TPU_GEMM_PREC choices
    (part of this cache key — slulint SLU105).  With a mesh, the dense
    math shards exactly like stream._kernel (batch-over-"snode",
    columns-over-"panel", pool via factor.pool_spec)."""
    batch, m, w, u = dims
    front_sharding = pivot_sharding = replicated = pool_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from superlu_dist_tpu.numeric.factor import pool_spec
        front_sharding = NamedSharding(mesh, P("snode", None, "panel"))
        pivot_sharding = NamedSharding(mesh, P("snode", None, None))
        replicated = NamedSharding(mesh, P(None, None))
        pool_sharding = pool_spec(mesh, pool_partition)

    def step(avals, pool, thresh, a_slot, a_flat, a_src, ws, off,
             child_off, child_slot, child_ub, rel):
        if pool_sharding is not None:
            pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
        out, pool, tiny = group_step(
            (batch, m, w, u), avals, pool, thresh,
            a_slot, a_flat, a_src, ws, off,
            (child_off, child_slot, child_ub, rel),
            front_sharding=front_sharding, pivot_sharding=pivot_sharding,
            replicated=replicated, pivot=pivot,
            gemm_prec=gemm_prec)
        if pool_sharding is not None:
            pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
        return out, pool, tiny

    # pool donated exactly like the streamed kernels: XLA scatters the
    # Schur write-back in place instead of copying pool_len entries
    return jax.jit(step, donate_argnums=(1,))


class MegaExecutor(StreamExecutor):
    """Callable factorization with a CLOSED compiled-program set.

    Drop-in for StreamExecutor on a single device (same call contract,
    same checkpoint/deadline/chaos/sentinel hooks, same async dispatch
    stream); ``n_kernels`` == the plan's bucket-set size, independent of
    group count and — on a closed plan — of matrix size."""

    _census_site = "mega._kernel"

    @staticmethod
    def _census_label(key) -> str:
        # the pool rung P is part of the label: the SLU121 peak-memory
        # verdict is dominated by the rung-padded Schur pool, so a
        # MemoryBudgetError (and the census memory column) must name the
        # offending bucket RUNG, not just the front geometry
        (b, m, w, u) = key[0]
        return f"lu b{b} m{m} w{w} u{u} P{key[3]}"

    def __init__(self, plan: FactorPlan, dtype="float64", mesh=None,
                 offload: str = "auto", pool_partition: bool = False,
                 host_flops=None, gemm_prec=None):
        self._mega_fns = {}
        self._spec = {}
        # host-share is off by construction: the per-bucket programs are
        # device-resident and the leading-leaf split would need per-group
        # placement of the packed metadata
        super().__init__(plan, dtype, mesh=mesh, offload=offload,
                         pool_partition=pool_partition,
                         granularity="group", host_flops=0.0,
                         gemm_prec=gemm_prec)
        self.granularity = "mega"

    # ---- canonical metadata packing -------------------------------------
    def _build_steps(self) -> list:
        plan = self.plan
        n_avals = len(plan.pattern_indices)
        # store rungs: program shapes must not encode exact matrix sizes
        self._pool_len = bucket_rung(max(plan.pool_size, 1), lo=8,
                                     growth=_STORE_GROWTH)
        self._avals_len = bucket_rung(max(n_avals, 1), lo=8,
                                      growth=_STORE_GROWTH)
        P, AV = self._pool_len, self._avals_len
        by_key: dict = {}
        for grp in plan.groups:
            by_key.setdefault((grp.w, grp.u), []).append(grp)
        for (w, u), grps in by_key.items():
            # per-bucket canonical axes: maxima over the bucket's groups,
            # rung-rounded so same-size-class matrices share programs
            B = bucket_rung(max(g.batch for g in grps), lo=1, growth=2.0)
            la = bucket_rung(max(len(g.a_src) for g in grps) or 1,
                             lo=64, growth=4.0)
            nset = max(len(g.children) for g in grps)
            cmax = max((len(cs.child_off) for g in grps
                        for cs in g.children), default=0)
            ubmax = max((cs.ub for g in grps for cs in g.children),
                        default=0)
            if nset:
                nset = bucket_rung(nset, lo=1, growth=2.0)
                cmax = bucket_rung(cmax, lo=1, growth=4.0)
            self._spec[(w, u)] = (B, la, (nset, cmax, ubmax))
        steps = []
        for grp in plan.groups:
            B, la, (nset, cmax, ubmax) = self._spec[(grp.w, grp.u)]
            # sentinels re-based onto the PADDED stores: the plan's
            # pool_size sentinel would land INSIDE the rung-padded pool
            off = np.where(np.asarray(grp.off) >= plan.pool_size, P,
                           grp.off)
            a = (_pad_to(grp.a_slot, la, B), _pad_to(grp.a_flat, la, 0),
                 _pad_to(grp.a_src, la, AV), _pad_to(grp.ws, B, 0),
                 _pad_to(off, B, P))
            co = np.full((nset, cmax), P, dtype=np.int64)
            csl = np.full((nset, cmax), B, dtype=np.int64)
            cub = np.ones(max(nset, 0), dtype=np.int64)
            rel = np.full((nset, cmax, ubmax), grp.m, dtype=np.int64)
            for si, cs in enumerate(grp.children):
                c = len(cs.child_off)
                co[si, :c] = cs.child_off
                csl[si, :c] = cs.child_slot
                cub[si] = cs.ub
                rel[si, :c, :cs.ub] = cs.rel
            key = ((B, grp.m, grp.w, grp.u), la, (nset, cmax, ubmax),
                   P, AV, self.dtype)
            steps.append((key, tuple(jnp.asarray(x) for x in a),
                          (jnp.asarray(co), jnp.asarray(csl),
                           jnp.asarray(cub), jnp.asarray(rel)),
                          grp.batch, False))
        return steps

    def _build_kernels(self, pivot, avals, pool, thresh) -> None:
        """Nothing ahead of the stream: each bucket program is AOT-staged
        by ``_get_kernel`` on first use (``prebake`` warms the whole set
        without a factorization)."""

    # ---- AOT program acquisition + census -------------------------------
    def _get_kernel(self, key, pivot, args):
        """AOT-stage the bucket's program on first use: trace → lower →
        XLA compile, timed SEPARATELY so the census (and the bench row)
        can distinguish a persistent-cache disk hit (compile ~0) from a
        cold build — the warm-start acceptance measurement."""
        fn = self._mega_fns.get((key, pivot))
        if fn is not None:
            return fn
        jfn = _mega_kernel(*key, pivot, self.gemm_prec, self.mesh,
                           self.pool_partition)
        sds = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in args)
        # program audit at AOT-stage time: a finding raises BEFORE the
        # XLA compile below ever runs (SLU_TPU_VERIFY_PROGRAMS=1)
        self._audit_program(self._census_site, self._census_label(key),
                            jfn, sds)
        with COMPILE_STATS.build(self._census_site, self._census_label(key),
                                 n_args=len(args)) as b:
            t0 = time.perf_counter()
            traced = jfn.trace(*sds)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            compiled = lowered.compile()
            b.trace_seconds, b.lower_seconds = t1 - t0, t2 - t1
            b.compile_seconds = time.perf_counter() - t2
        self._mega_fns[(key, pivot)] = compiled
        return compiled

    def _census_pending(self, key, pivot) -> bool:
        return False            # accounted inside _get_kernel (AOT)

    def prebake(self) -> int:
        """Compile every bucket program WITHOUT running a factorization
        (shape specs only) — the fleet warm-start primitive
        (scripts/warm_compile_cache.py): with the persistent compile
        cache enabled the whole closed set lands on disk, so any later
        process whose buckets match compiles nothing.  Returns the
        number of programs now resident."""
        from superlu_dist_tpu.ops.dense import pivot_kernel
        pivot = pivot_kernel()
        idt = jnp.asarray(np.zeros(0, dtype=np.int64)).dtype
        dts = jnp.dtype(self.dtype)
        rdt = dts.type(0).real.dtype
        Sds = jax.ShapeDtypeStruct
        for key in sorted({k for k, _, _, _, _ in self._steps}, key=str):
            (B, m, w, u), la, (nset, cmax, ubmax), P, AV, _ = key
            args = (Sds((AV,), dts), Sds((P,), dts), Sds((), rdt),
                    Sds((la,), idt), Sds((la,), idt), Sds((la,), idt),
                    Sds((B,), idt), Sds((B,), idt),
                    Sds((nset, cmax), idt), Sds((nset, cmax), idt),
                    Sds((nset,), idt), Sds((nset, cmax, ubmax), idt))
            self._get_kernel(key, pivot, args)
        return len(self._mega_fns)

    # ---- padded-store plumbing ------------------------------------------
    def _prep_avals(self, avals):
        av = jnp.asarray(avals, dtype=self.dtype)
        return jnp.zeros(self._avals_len,
                         dtype=self.dtype).at[:av.shape[0]].set(av)

    def _ckpt_pool(self, pool):
        # frontiers must stay executor-portable (stream resumes a mega
        # checkpoint bitwise and vice versa): store the UNPADDED pool
        return pool[:self.plan.pool_size]

    def _apply_resume(self, resume, pool):
        start, fronts, pool, tiny = super()._apply_resume(resume, pool)
        if pool.shape[0] < self._pool_len:
            pool = jnp.zeros(self._pool_len,
                             dtype=self.dtype).at[:pool.shape[0]].set(pool)
        return start, fronts, pool, tiny

    def _retrace_begin(self) -> int:
        return len(self._mega_fns)

    @property
    def executed_flops(self) -> float:
        return float(sum(self._spec[(g.w, g.u)][0] * _front_flops(g.w, g.u)
                         for g in self.plan.groups))

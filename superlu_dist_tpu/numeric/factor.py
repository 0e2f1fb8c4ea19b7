"""Level-batched multifrontal numeric factorization on the accelerator.

The execution analog of pdgstrf (SRC/pdgstrf.c:243) — but where the
reference runs an MPI look-ahead pipeline of per-panel BLAS calls, this
walks the elimination-tree levels bottom-up and, per (level, bucket) group,
issues assembly gathers, one batched dense partial LU (ops.dense), and a
strided Schur write-back.  All arrays stay resident on the device; the
update pool plays the role of the reference's bigU/bigV GEMM buffers
(pdgstrf.c:770-884) and the device-computed extend-add indices the role of
the dscatter_l/u index arithmetic (SRC/dscatter.c:111-290).

Four executors share the same per-group step (`group_step`):
  * make_factor_fn — the whole factorization traced into ONE jittable XLA
    program (best for moderate plans);
  * stream.StreamExecutor — one small jitted kernel per shape key, groups
    streamed through asynchronously (best on real TPU where giant programs
    compile slowly);
  * mega.MegaExecutor — shape-closed bucketed programs, O(1) compile
    count across matrices (and, since the SPMD tier, under a mesh);
  * parallel.spmd.SpmdFactorExecutor — the shard_map tier: the whole
    factorization as ONE SPMD program over the mesh, slots block-cyclic
    over the devices and the collectives in-program ops XLA can overlap
    with compute (the pdgstrf look-ahead shape).
"""

from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from superlu_dist_tpu.numeric.plan import (LANES, FactorPlan, front_dims,
                                           lane_pad, pool_block)
from superlu_dist_tpu.obs.trace import get_tracer
from superlu_dist_tpu.ops.dense import group_partial_factor


def extend_add_set(fx, pool, m, ub, child_off, child_slot, rel):
    """One child-set's extend-add: gather each child's padded ub×ub Schur
    block from the pool and scatter-add it into the parent fronts at
    (rel[c,i], rel[c,j]).  SHARED MACHINERY: ``group_step`` unrolls a
    Python loop of these per group (one call per ChildSet), and the mega
    executor (numeric/mega.py) lax.scan's the SAME function over uniform
    padded child tables with a TRACED ``ub`` — keep it shape-polymorphic
    in (C, UB) and exact in the per-child gather indices
    (off + i·lane_pad(ub) + j), which is what makes the two executors
    bitwise-identical.

    ``fx`` is the batch of fronts FLAT in the dump layout of
    ``group_step`` (``front_dims``): the rel sentinel (== m) lands in the
    dump row/column, discarded afterwards.  The scatter indices are
    non-decreasing — a set's children are in ascending slot order
    (numeric/plan.py), each child's rel row ascends to its sentinels,
    the lane padding of a pool block maps to the sentinel too, and a
    padded child (slot == batch) lies past the end and is dropped — so
    the scatter is declared ``indices_are_sorted``.  The index arrays
    are built as (C, UB, lane_pad(UB)) and merged along the lane-aligned
    minor axis: merging a (C, UB, UB) array costs the TPU compiler a
    relayout of ~25 s at UB ≈ 2700, index arrays derived from one long
    iota get folded into constants at compile time, and an unsorted
    scatter costs ~15 s on its own."""
    c, ubmax = rel.shape
    rows, cols = front_dims(m)
    stride = lane_pad(ubmax)
    if isinstance(ub, int) and ub == ubmax:
        # static block size: each child's block is one contiguous pool
        # slab, read as a slice (a slot past the pool clamps — always a
        # padded child whose scatter below is dropped)
        vals = jax.vmap(
            lambda o: jax.lax.dynamic_slice(pool, (o,), (ub * stride,)))(
                child_off)
    else:
        # per-child gather at the child's REAL row stride (the per-set
        # bucket in the mega scan), so entries past a child's real block
        # read out of its pool slab — always paired with a rel
        # sentinel, hence dumped below
        row_stride = -(-ub // LANES) * LANES       # lane_pad, traced
        src = (child_off[:, None, None]
               + jnp.arange(ubmax)[:, None] * row_stride
               + jnp.arange(stride)).reshape(c, ubmax * stride)
        vals = pool.at[src].get(mode="fill", fill_value=0)
    rel_cols = jnp.pad(rel, ((0, 0), (0, stride - ubmax)),
                       constant_values=m)
    idx = (child_slot[:, None, None] * (rows * cols)
           + rel[:, :, None] * cols + rel_cols[:, None, :])
    return fx.at[idx.reshape(c, ubmax * stride)].add(
        vals, mode="drop", indices_are_sorted=True)


def group_step(dims, avals, pool, thresh, a_slot, a_flat, a_src, ws, off,
               children, front_sharding=None, pivot_sharding=None,
               replicated=None, pivot="blocked", gemm_prec="highest",
               write_back=True):
    """One (level, bucket) group: assemble + factor + write back.

    dims = (batch, m, w, u) static; `children` is either a list of
    (ub, child_off, child_slot, rel) with device arrays (the fused and
    streamed executors — one unrolled extend-add per set), or a 4-tuple
    of STACKED tables (child_off (S,C), child_slot (S,C), child_ub (S,),
    rel (S,C,UB)) which the mega executor folds in with ONE lax.scan —
    same per-set arithmetic, program size independent of the set count.
    Index padding convention (used by the streamed executor): scatter
    slots == batch and gather sources past the array end are
    dropped/filled — all index arithmetic keeps OOB entries OOB (rel
    sentinel == m lands in the discarded dump row/column).

    Every scatter into the fronts declares its indices sorted (see
    extend_add_set): the A-entry maps arrive sorted by (slot, position)
    from the plan, padding past the end.

    ``gemm_prec`` is the caller-resolved GEMM-precision ladder tier,
    baked into the cached jitted factories' keys, never read from env
    here (slulint SLU102/SLU105).

    ``write_back=False`` (the SPMD per-shard path) skips the pool
    write and returns the raw (batch, pool_block(u)) Schur blocks in the
    pool's position instead (None when u == 0): inside shard_map each
    device factors only its slot partition, so the full-order pool
    write is replayed by the caller AFTER the all-gather — keeping the
    exact write sequence (and hence bitwise factors) of the
    write_back=True lowering every other executor runs.
    """
    batch, m, w, u = dims
    dt = pool.dtype
    wsc = jax.lax.with_sharding_constraint
    rows, cols = front_dims(m)

    # fronts flat in the dump layout (extend_add_set)
    fx = jnp.zeros((batch * rows * cols,), dtype=dt)
    if replicated is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        fx = wsc(fx, NamedSharding(replicated.mesh, P()))
    # identity columns for pivot-block padding (cols ws..w), computed on
    # device so padded batch slots (ws == 0) become identity fronts
    k = jnp.arange(m)
    diag_mask = (k[None, :] >= ws[:, None]) & (k[None, :] < w)
    # one flat index vector: under a mesh, XLA's SPMD partitioner
    # splits a (batch, m) index array along the batch axis and
    # miscompiles the scatter (jax 0.9; tests/test_parallel.py)
    diag = (jnp.arange(batch)[:, None] * (rows * cols)
            + k * (cols + 1)).reshape(-1)
    fx = fx.at[diag].add(diag_mask.astype(dt).reshape(-1),
                         indices_are_sorted=True, unique_indices=True)
    if a_src.shape[0]:
        vals = avals.at[a_src].get(mode="fill", fill_value=0)
        # a_flat is the position in the (m, m) front; padding entries
        # (slot == batch) land past the end and are dropped
        idx = a_slot * (rows * cols) + (a_flat // m) * cols + a_flat % m
        fx = fx.at[idx].add(vals, mode="drop", indices_are_sorted=True,
                            unique_indices=True)
    if isinstance(children, tuple):
        # stacked child tables (mega executor): scan the shared per-set
        # extend-add — the sets fold into f in the same sequence the
        # Python loop below runs them, so the factors stay bitwise equal
        c_off, c_slot, c_ub, c_rel = children
        if c_off.shape[0]:
            def body(fc, xs):
                co, cs, ub, r = xs
                return extend_add_set(fc, pool, m, ub, co, cs, r), None
            fx, _ = jax.lax.scan(body, fx, (c_off, c_slot, c_ub, c_rel))
    else:
        for (ub, child_off, child_slot, rel) in children:
            fx = extend_add_set(fx, pool, m, ub, child_off, child_slot, rel)
    f = fx.reshape(batch, rows, cols)[:, :m, :m]
    if front_sharding is not None:
        f = wsc(f, front_sharding)
    lpanel, upanel, schur, counts = group_partial_factor(
        f, thresh, w, front_sharding=front_sharding,
        pivot_sharding=pivot_sharding, pivot=pivot, gemm_prec=gemm_prec)
    # counts is (batch, w) per-column tiny flags; identity-padding columns
    # (col >= ws, incl. whole padded batch slots with ws == 0) are unit
    # pivots — don't let a thresh > 1 count them as tiny
    tiny = jnp.sum(jnp.where(jnp.arange(w)[None, :] < ws[:, None], counts, 0))
    if u > 0:
        # the pool block layout (plan.pool_block): lane-padded rows
        vals = jnp.pad(schur, ((0, 0), (0, 0), (0, lane_pad(u) - u)))
        vals = vals.reshape(batch, pool_block(u))
        if replicated is not None:
            vals = wsc(vals, replicated)
        if not write_back:
            return (lpanel, upanel), vals, tiny
        pool = pool_write(pool, off, vals)
    elif not write_back:
        return (lpanel, upanel), None, tiny
    return (lpanel, upanel), pool, tiny


def pool_write(pool, off, vals):
    """Write slot s's Schur block ``vals[s]`` into the pool at ``off[s]``
    — one contiguous slice per slot, which the TPU compiler lowers in
    well under a second where the equivalent element scatter costs
    ~15 s.  A slot whose offset lies past the pool (padding) leaves the
    pool unchanged."""
    size, n = pool.shape[0], vals.shape[1]

    def body(s, pool):
        o = off[s]
        cur = jax.lax.dynamic_slice(pool, (o,), (n,))
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(o < size, vals[s], cur), (o,))

    return jax.lax.fori_loop(0, vals.shape[0], body, pool)


def pool_spec(mesh, pool_partition: bool):
    """The Schur pool's sharding: replicated, or 1-D over ALL mesh devices
    (pool_partition — per-chip pool memory divides by the device count).
    Single definition shared by both executors; returns None without a
    mesh."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(
        mesh, P(tuple(mesh.axis_names)) if pool_partition else P(None))


def _group_arrays(grp):
    children = [(cs.ub, jnp.asarray(cs.child_off), jnp.asarray(cs.child_slot),
                 jnp.asarray(cs.rel)) for cs in grp.children]
    return (jnp.asarray(grp.a_slot), jnp.asarray(grp.a_flat),
            jnp.asarray(grp.a_src), jnp.asarray(grp.ws),
            jnp.asarray(grp.off), children)


@dataclasses.dataclass
class NumericFactorization:
    """LU factors as packed front batches (the dLUstruct_t analog,
    superlu_ddefs.h:186-191)."""

    plan: FactorPlan
    fronts: list              # per group: (lpanel (B,M,w), upanel (B,w,u))
                              # — packed L (diag block over L21) and U12;
                              # the eliminated A22 is never stored (its
                              # Schur update lives transiently in the pool)
    tiny_pivots: int
    dtype: object
    finite: bool = True       # False => an exact zero pivot propagated
                              # (only possible with replace_tiny=False)
    info_col: int = -1        # first zero-pivot column (0-based, final
                              # labeling) when not finite — the reference's
                              # info>0 = first i with U(i,i)==0
                              # (pdgstrf.c:1920-1924, Allreduce MIN)
    host_fronts: list = None  # lazily pulled numpy copies for the host solve
    resumed_groups: int = 0   # dispatch groups restored from a durable
                              # checkpoint frontier instead of recomputed
                              # (persist/checkpoint.py; 0 = fresh run)
    executor: str = ""        # class name of the factor executor that
                              # ran (StreamExecutor, SpmdFactorExecutor…)
    gemm_prec: str = "highest"  # GEMM-precision ladder tier the Schur
                              # updates ran at (ops/dense.gemm_precision)
                              # — recorded so the BERR gate / escalation
                              # rung and the SolveReport can name the
                              # tier the delivered answer rests on

    @property
    def on_host(self) -> bool:
        """True when the factors ALL live in host memory (the executor
        streamed them off-device — offload mode — or we run on the CPU
        backend).  A host-share split (stream.py SLU_TPU_HOST_FLOPS)
        leaves only the leading leaf panels as numpy — that is a
        device-resident factorization and must keep the device solve."""
        return bool(self.fronts) and all(
            isinstance(lp, np.ndarray) for lp, _ in self.fronts)

    def pull_to_host(self):
        """Transfer factors to host once (the dSolveInit analog,
        SRC/pdutil.c:690 — solve-side setup cached across solves)."""
        if self.host_fronts is None:
            self.host_fronts = [(np.asarray(lp), np.asarray(up))
                                for lp, up in self.fronts]
        return self.host_fronts


def make_factor_fn(plan: FactorPlan, dtype="float64", mesh=None,
                   pool_partition: bool = False, gemm_prec=None):
    """Build the whole numeric factorization as ONE jittable function.

    Returns fn(avals, thresh) -> (fronts_tuple, tiny_count).  The plan's
    index maps are passed as PROGRAM ARGUMENTS (latched on the returned
    wrapper), not closed over: a closure-captured device array becomes a
    CONSTANT of the jaxpr, so the compiled program identifies the matrix
    — the per-matrix-capture pattern slulint SLU112 polices, which
    defeats cross-matrix program reuse and duplicates the maps into the
    executable.  If `mesh` is a jax.sharding.Mesh with axes ("snode", "panel"),
    the dense factor math is sharded batch-over-"snode" and
    columns-over-"panel" — the 2D block-cyclic layout analog (SURVEY.md
    §2.4) — while every irregular scatter/gather is pinned replicated
    (XLA's SPMD partitioner miscompiles scatter/gather with sharded minor
    dims, jax 0.9.0; they are bandwidth-trivial next to the GEMMs).

    pool_partition=True shards the Schur update pool itself across ALL
    mesh devices (1-D, so the partitioner handles it — verified equal to
    the replicated result on a virtual mesh).  This divides the pool's
    HBM footprint by the device count — the path to the n≈1M problem
    class, whose ~27 GB pool exceeds one chip (the reference's analog:
    no rank holds the whole factor, SURVEY.md §5 scaling) — at the cost
    of extra collectives per extend-add.
    """
    dtype = jnp.dtype(dtype)
    plan.check_index_width()
    sharding = pivot_sharding = replicated = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(mesh, P("snode", None, "panel"))
        pivot_sharding = NamedSharding(mesh, P("snode", None, None))
        pool_sharding = pool_spec(mesh, pool_partition)
        replicated = NamedSharding(mesh, P(None, None))
    arrays = [_group_arrays(grp) for grp in plan.groups]
    # flatten the index maps into one static-layout argument list: the
    # per-group child counts (ubs) are program STRUCTURE, the arrays are
    # program INPUTS — so the jaxpr carries no per-matrix constants
    # (slulint SLU112) and dead-input/donation accounting sees them
    flat_args = []
    child_meta = []
    for (a_slot, a_flat, a_src, ws, off, children) in arrays:
        flat_args.extend((a_slot, a_flat, a_src, ws, off))
        child_meta.append(tuple(ub for ub, _, _, _ in children))
        for (_, child_off, child_slot, rel) in children:
            flat_args.extend((child_off, child_slot, rel))
    flat_args = tuple(flat_args)
    # SLU_TPU_PIVOT_KERNEL / SLU_TPU_GEMM_PREC resolved HERE, in the
    # uncached factory, and closed over as constants — get_executor keys
    # the fused executor on them, and the traced body must not read env
    # (slulint SLU102/SLU105)
    from superlu_dist_tpu.ops.dense import gemm_precision, pivot_kernel
    pivot = pivot_kernel()
    gemm_prec = gemm_precision(gemm_prec)

    def factor_fused(avals, thresh, *flat):
        avals = avals.astype(dtype)
        pool = jnp.zeros(plan.pool_size, dtype=dtype)
        if mesh is not None:
            pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
        fronts = []
        tiny = jnp.zeros((), jnp.int32)
        i = 0
        for grp, ubs in zip(plan.groups, child_meta):
            a_slot, a_flat, a_src, ws, off = flat[i:i + 5]
            i += 5
            children = []
            for ub in ubs:
                children.append((ub, flat[i], flat[i + 1], flat[i + 2]))
                i += 3
            packed, pool, t = group_step(
                (grp.batch, grp.m, grp.w, grp.u), avals, pool, thresh,
                a_slot, a_flat, a_src, ws, off, children,
                front_sharding=sharding, pivot_sharding=pivot_sharding,
                replicated=replicated, pivot=pivot, gemm_prec=gemm_prec)
            if mesh is not None:
                pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
            fronts.append(packed)
            tiny = tiny + t
        return tuple(fronts), tiny

    jfn = jax.jit(factor_fused)
    # the fused path keeps real batch sizes (no pow-2 pad); shape padding
    # is already inside _front_flops' padded (w, u) dims
    from superlu_dist_tpu.symbolic.symbfact import _front_flops
    executed = float(sum(g.batch * _front_flops(g.w, g.u)
                         for g in plan.groups))

    built = []

    def traced(avals, thresh):
        """Kernel-shape telemetry for the one-program executor: the whole
        factorization is a single dispatch, so it records one issue span
        plus one aggregate kernel span (blocking only when a profiling
        tracer is on — the warm disabled path returns the async jitted
        call untouched).  The FIRST call additionally lands in the
        compile census: jit compiles synchronously inside it, so its
        wall time IS the build cost of the fused program."""
        tracer = get_tracer()
        cold = not built
        if not (tracer.enabled or cold):
            return jfn(avals, thresh, *flat_args)
        import contextlib
        import time

        from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
        if cold:
            # program audit (SLU_TPU_VERIFY_PROGRAMS=1): one abstract
            # trace before the program first runs — no dead args (the
            # caller may retain avals; the maps live on the executor)
            from superlu_dist_tpu.utils.programaudit import maybe_audit
            maybe_audit(
                "make_factor_fn",
                f"fused g{len(plan.groups)} {str(dtype)} {gemm_prec}", jfn,
                (avals, thresh, *flat_args),
                mesh_axes=tuple(mesh.axis_names) if mesh is not None
                else ())
        t0 = time.perf_counter()
        # same label the audit notes use (gemm_prec included), so the
        # census join that attaches peak_bytes_est to this row holds
        with (COMPILE_STATS.build(
                "make_factor_fn",
                f"fused g{len(plan.groups)} {str(dtype)} {gemm_prec}",
                n_args=2) if cold else contextlib.nullcontext()):
            out = jfn(avals, thresh, *flat_args)
        t_issue = time.perf_counter() - t0
        if cold:
            built.append(True)
        if not tracer.enabled:
            return out
        tracer.complete("issue fused", "dispatch", t0, t_issue,
                        groups=len(plan.groups))
        if tracer.profiling:
            jax.block_until_ready(out[0])
            tracer.complete("factor-fused", "kernel", t0,
                            time.perf_counter() - t0,
                            n_groups=len(plan.groups), aggregate=True,
                            executed_flops=executed,
                            structural_flops=float(plan.flops),
                            padding=round(executed / max(float(plan.flops),
                                                         1.0), 4))
        return out

    traced.executor_name = "make_factor_fn"
    return traced


def get_executor(plan: FactorPlan, dtype="float64", executor: str = "auto",
                 mesh=None, pool_partition: bool = False, gemm_prec=None):
    """Executor for a plan, cached on the plan (SamePattern reuse tier).

    executor: "fused" (one XLA program — fast dispatch, compile grows with
    plan size), "stream" (per-bucket kernels — compile count is bounded,
    right for real TPU where program compile is expensive), "mega"
    (bucketed shape-closed programs, O(1) compile count), "spmd" (the
    shard_map tier, parallel/spmd.py: one compiled program per group
    with the collectives as in-program ops), or "auto".  Auto picks
    spmd on a single-process mesh (unless SLU_TPU_SPMD=0 or the pool is
    partitioned), stream on multi-process meshes and accelerators, and
    fused on single-controller CPU.  A mesh spanning processes keeps
    stream for the same reason real TPU does: the fused whole-program
    jit's compile time grows with the plan (an n≈1e5 SPMD program took
    >60 min on XLA:CPU), while the streamed kernels' compile count is
    bounded by distinct shape keys.  mesh shards every executor over
    ("snode", "panel"); pool_partition shards the Schur pool across all
    mesh devices (see make_factor_fn).
    """
    if executor not in ("auto", "fused", "stream", "mega", "spmd"):
        raise ValueError(f"executor must be auto|fused|stream|mega|spmd, "
                         f"got {executor!r}")
    multiproc = mesh is not None and jax.process_count() > 1
    if executor == "auto":
        from superlu_dist_tpu.parallel.spmd import spmd_mode
        if (mesh is not None and not multiproc and not pool_partition
                and spmd_mode()):
            executor = "spmd"
        else:
            executor = ("fused" if jax.default_backend() == "cpu"
                        and not multiproc else "stream")
    if executor == "spmd" and (mesh is None or multiproc or pool_partition):
        # the shard_map tier is single-controller over a local mesh and
        # replays the full-order pool on every device (its bitwise
        # contract) — no mesh, a multi-process mesh, or a partitioned
        # pool keep the streamed GSPMD kernels
        executor = "stream"
    cache = getattr(plan, "_factor_fns", None)
    if cache is None:
        cache = plan._factor_fns = {}
    from superlu_dist_tpu.ops.dense import gemm_precision, pivot_kernel
    from superlu_dist_tpu.utils.options import env_float
    # every executor bakes the GEMM-precision tier into its compiled
    # programs, so it is part of its identity (the escalation rung's
    # refactor-at-the-next-tier relies on getting a FRESH executor); the
    # fused executor additionally bakes the pivot-kernel choice, which
    # StreamExecutor re-reads per call (stream._kernel / _level_fns key
    # on it)
    gemm_prec = gemm_precision(gemm_prec)
    key = (str(jnp.dtype(dtype)), executor, mesh, bool(pool_partition),
           gemm_prec,
           pivot_kernel() if executor == "fused" else None,
           # StreamExecutor latches the host-share threshold at
           # construction — a changed SLU_TPU_HOST_FLOPS needs a new one
           env_float("SLU_TPU_HOST_FLOPS")
           if executor == "stream" else None)
    fn = cache.get(key)
    if fn is None:
        if executor == "stream":
            from superlu_dist_tpu.numeric.stream import StreamExecutor
            fn = StreamExecutor(plan, dtype, mesh=mesh,
                                pool_partition=pool_partition,
                                gemm_prec=gemm_prec)
        elif executor == "mega":
            from superlu_dist_tpu.numeric.mega import MegaExecutor
            fn = MegaExecutor(plan, dtype, mesh=mesh,
                              pool_partition=pool_partition,
                              gemm_prec=gemm_prec)
        elif executor == "spmd":
            from superlu_dist_tpu.parallel.spmd import SpmdFactorExecutor
            fn = SpmdFactorExecutor(plan, dtype, mesh,
                                    gemm_prec=gemm_prec)
        else:
            fn = make_factor_fn(plan, dtype, mesh=mesh,
                                pool_partition=pool_partition,
                                gemm_prec=gemm_prec)
        cache[key] = fn
    return fn


def numeric_factorize(plan: FactorPlan, pattern_values: np.ndarray,
                      anorm: float, dtype="float64",
                      replace_tiny: bool = True,
                      executor: str = "auto",
                      mesh=None,
                      pool_partition: bool = False,
                      check_finite: bool = True,
                      ckpt_dir: str | None = None,
                      ckpt_every: int = 0,
                      resume_from: str | None = None,
                      deadline=None,
                      gemm_prec: str | None = None) -> NumericFactorization:
    """Factor with values aligned to plan.pattern_indices.

    anorm: ‖A‖ for the GESP tiny-pivot threshold sqrt(eps)·‖A‖
    (reference pdgstrf2.c:218: thresh = eps·‖A‖; we use the sqrt variant of
    ReplaceTinyPivot so f32 factors retain half their digits).
    With replace_tiny=False an exact zero pivot propagates inf/nan; the
    result is flagged non-finite (the reference's info>0 singularity path,
    pdgstrf.c:234-241).

    check_finite arms the non-finite sentinel: with ReplaceTinyPivot
    active a NaN/Inf in the factors means overflow or NaN input (never
    expected singularity), so the cheap isfinite reductions below trip a
    structured NumericBreakdownError naming the offending supernode
    instead of letting NaN propagate through every later front.

    Crash consistency (persist/, docs/RELIABILITY.md): ``ckpt_every`` /
    ``ckpt_dir`` arm a FactorCheckpointer flushing the completed-group
    frontier every K groups (and on breakdown/deadline/SIGTERM);
    ``resume_from`` loads a checkpoint, verifies its plan fingerprint
    AND value digest against THIS call's inputs, and restarts the
    stream from the durable frontier — bitwise-identical factors to an
    uninterrupted run.  ``deadline`` is a utils.deadline.Deadline
    polled between dispatch groups.  Checkpointing/resume have group
    boundaries only on the streamed executor, so arming them forces
    ``executor="stream"``.
    """
    dtype = jnp.dtype(dtype)
    real_dtype = jnp.dtype(dtype).type(0).real.dtype
    eps = jnp.finfo(real_dtype).eps
    # GEMM-precision ladder tier (ops/dense.gemm_precision): resolved
    # ONCE here so the executor, the checkpoint identity and the result
    # record all agree on the arithmetic this factorization ran
    from superlu_dist_tpu.ops.dense import gemm_precision
    gemm_prec = gemm_precision(gemm_prec)
    tracer = get_tracer()
    if tracer.enabled:
        # schedule telemetry span: what the dispatch stream below is
        # shaped like (groups before/after aggregation, occupancy,
        # padding, critical path) — the same block Stats.report prints
        import time
        tracer.complete("schedule", "phase", time.perf_counter(), 0.0,
                        **plan.schedule_stats(itemsize=dtype.itemsize))
    thresh = jnp.asarray(
        np.sqrt(float(eps)) * max(anorm, 1e-300) if replace_tiny else 0.0,
        dtype=real_dtype)
    # failure-domain chaos injection (testing/chaos.py, SLU_TPU_CHAOS):
    # the NaN poke rewrites the values BEFORE the checkpointer latches
    # its value digest, so a frontier computed from poisoned values can
    # never be resumed against clean ones
    from superlu_dist_tpu.testing.chaos import get_chaos
    chaos = get_chaos()
    if chaos is not None:
        pattern_values = chaos.poke_nan(plan, pattern_values)
    ckpt = None
    want_ckpt = bool(ckpt_dir) or ckpt_every > 0
    if want_ckpt or resume_from:
        # checkpoints need per-group boundaries: the streamed and mega
        # executors have them, the fused and spmd whole-program jits
        # do not
        if executor in ("auto", "fused", "spmd"):
            executor = "stream"
    if want_ckpt:
        from superlu_dist_tpu.persist.checkpoint import FactorCheckpointer
        # the GEMM tier is part of the frontier's numeric identity: a
        # bf16 frontier spliced under highest arithmetic would silently
        # break the bitwise-resume guarantee
        ckpt = FactorCheckpointer(ckpt_dir or ".slu_ckpt", plan,
                                  pattern_values, thresh, dtype,
                                  every=int(ckpt_every),
                                  gemm_prec=gemm_prec)
    resume = None
    if resume_from:
        from superlu_dist_tpu.persist.checkpoint import load_checkpoint
        resume = load_checkpoint(resume_from, plan=plan,
                                 pattern_values=pattern_values,
                                 thresh=thresh, dtype=dtype,
                                 gemm_prec=gemm_prec)
    avals = jnp.asarray(pattern_values, dtype=dtype)
    fn = get_executor(plan, dtype, executor, mesh=mesh,
                      pool_partition=pool_partition, gemm_prec=gemm_prec)
    if hasattr(fn, "check_finite"):
        # streamed executor: also sentinel each offloaded group as it
        # lands on the host (early abort — see stream._emit_front),
        # plus the crash-consistency hooks (one-shot resume state)
        fn.check_finite = bool(check_finite and replace_tiny)
        fn.checkpoint = ckpt
        fn.resume = resume
        fn.deadline = deadline
        fn.chaos = chaos
    elif deadline is not None:
        # fused executor: one dispatch, so the only boundaries are
        # before/after the whole program
        deadline.poll(where="fused factorization")
    try:
        fronts_out, tiny_total = fn(avals, thresh)
    except BaseException:
        if ckpt is not None:
            # keep the flushed frontier on disk but deregister — a later
            # factorization's SIGTERM flush must not resurrect stale refs
            ckpt.complete(cleanup=False)
        raise
    finally:
        if hasattr(fn, "check_finite"):
            # the hooks are per-call state; a reused executor must not
            # carry them into the next factorization
            fn.checkpoint = fn.resume = fn.deadline = fn.chaos = None
    fronts_out = list(fronts_out)
    finite = True
    info_col = -1
    if not replace_tiny:
        finite, info_col = localize_singularity(plan, fronts_out)
    elif check_finite and not fronts_finite(fronts_out):
        from superlu_dist_tpu.utils.errors import NumericBreakdownError
        sn, col = localize_nonfinite(plan, fronts_out)
        ck_path = None
        if ckpt is not None:
            ck_path = ckpt.flush_latest("numeric-breakdown")
            ckpt.complete(cleanup=False)
        err = NumericBreakdownError(supernode=sn, col=col,
                                    where="numeric factorization")
        err.checkpoint_path = ck_path
        raise err
    if ckpt is not None:
        # completed: the durable artifact of a finished factorization is
        # the saved handle (persist.save_lu), not a stale frontier
        ckpt.complete(cleanup=True)
    return NumericFactorization(plan=plan, fronts=fronts_out,
                                tiny_pivots=int(tiny_total), dtype=dtype,
                                finite=finite, info_col=info_col,
                                resumed_groups=(resume.k if resume is not None
                                                else 0),
                                executor=getattr(fn, "executor_name",
                                                 type(fn).__name__),
                                gemm_prec=gemm_prec)


def fronts_finite(fronts) -> bool:
    """Cheap isfinite sentinel over factored panels: one all-reduce per
    group, device-resident panels reduced device-side (a few scalar
    transfers — O(panel bytes) reads, trivial next to the factorization's
    O(n·w²) flops)."""
    flags = []
    for lp, up in fronts:
        if isinstance(lp, np.ndarray):
            if not (np.isfinite(lp).all() and np.isfinite(up).all()):
                return False
        else:
            flags.append(jnp.isfinite(lp).all() & jnp.isfinite(up).all())
    if flags:
        return bool(np.all(jax.device_get(flags)))
    return True


def localize_nonfinite(plan: FactorPlan, fronts):
    """Earliest contaminated supernode over all fronts: returns
    (supernode, first global column), or (-1, -1) if everything is finite.
    The localization mirrors localize_singularity's per-SLOT attribution —
    an unrelated subtree batched in the same group must not be blamed."""
    sn_start = plan.sf.sn_start
    best_sn, best_col = -1, -1
    for grp, (lp, up) in zip(plan.groups, fronts):
        lph = np.asarray(lp)
        nf = ~np.isfinite(lph.reshape(lph.shape[0], -1)).all(axis=1)
        nf |= ~np.isfinite(np.asarray(up).reshape(
            lph.shape[0], -1)).all(axis=1)
        if nf.any():
            sns = np.asarray(grp.sns)[np.nonzero(nf)[0]]
            sn = int(sns[np.argmin(sn_start[sns])])
            col = int(sn_start[sn])
            if best_col < 0 or col < best_col:
                best_sn, best_col = sn, col
    return best_sn, best_col


def localize_singularity(plan: FactorPlan, fronts):
    """Zero-pivot detection + localization over factored fronts.

    A zero or non-finite U diagonal in a real (non-padding) column; the
    earliest such global column is the reference's info>0
    first-zero-pivot index (pdgstrf.c:1920-1924).  A zero pivot in the
    LAST column of a front divides nothing during factorization, so an
    isfinite scan alone would miss it.  Returns (finite, info_col)."""
    bad_cols = []
    sn_start = plan.sf.sn_start
    for grp, (lp, up) in zip(plan.groups, fronts):
        lph = np.asarray(lp)
        diag = np.diagonal(lph[:, :grp.w, :grp.w], axis1=1, axis2=2)
        bad = (diag == 0) | ~np.isfinite(diag)
        bad &= np.arange(grp.w)[None, :] < np.asarray(grp.ws)[:, None]
        if bad.any():
            slots, cols = np.nonzero(bad)
            bad_cols.append(int((sn_start[grp.sns[slots]] + cols).min()))
        else:
            # off-diagonal-only contamination: attribute per SLOT, not
            # per group — an unrelated subtree batched in the same
            # group must not shift min(bad_cols) below the true pivot
            # (contamination only flows to ancestors, whose columns
            # are larger than the zero pivot's)
            nf = ~np.isfinite(lph.reshape(lph.shape[0], -1)).all(axis=1)
            nf |= ~np.isfinite(np.asarray(up).reshape(
                lph.shape[0], -1)).all(axis=1)
            if nf.any():
                bad_cols.append(int(sn_start[grp.sns[nf]].min()))
    if bad_cols:
        return False, min(bad_cols)
    return True, -1


def factor_flops(plan: FactorPlan) -> float:
    """Flop count for stats (the ops[FACT] analog, SRC/util.c:513)."""
    return plan.flops


def query_space(numeric: NumericFactorization) -> dict:
    """Memory held by the factorization — the dQuerySpace_dist analog
    (SRC/dmemory_dist.c:73): packed-front (L+U) bytes plus the transient
    Schur update pool (the reference's 'expansions'/buffer gauges)."""
    itemsize = np.dtype(numeric.dtype).itemsize
    front_b = sum(int(np.prod(lp.shape)) + int(np.prod(up.shape))
                  for lp, up in numeric.fronts) * itemsize
    pool_b = int(numeric.plan.pool_size) * itemsize
    return {"for_lu_bytes": front_b, "pool_bytes": pool_b,
            "total_bytes": front_b + pool_b}

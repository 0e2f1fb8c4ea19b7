"""Expert driver: the full solve pipeline with factorization-reuse tiers.

Analog of pdgssvx (SRC/pdgssvx.c:505): equilibrate → row-permute (maximum
product matching with scalings) → column-order → symbolic → plan ("distribute")
→ numeric factor → solve → iterative refinement, with the reference's Fact
reuse modes (superlu_defs.h:489-510):

  DOFACT                  — everything from scratch
  SamePattern             — reuse column order + symbolic + plan
  SamePattern_SameRowPerm — additionally reuse scalings + row permutation,
                            only redo the numeric factorization
  FACTORED                — reuse the numeric factors; solve + refine only

Permutation algebra (careful!): with equilibration scalings Dr, Dc, matching
scalings r1, c1 and row order ρ, the factored matrix is
    M = Pπ · (diag(R) A diag(C))[ρ] · Pπᵀ,  R = r1·dr, C = dc·c1
where π is the fill-reducing + postorder column permutation.  Then
A·x = b is solved as
    d = (R ⊙ b)[ρ][π] ;  M·ẑ = d ;  z[π] = ẑ ;  x = C ⊙ z.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from superlu_dist_tpu.obs.trace import get_tracer
from superlu_dist_tpu.sparse.formats import SparseCSR, symmetrize_pattern
from superlu_dist_tpu.utils.options import (
    Options, Fact, RowPerm, IterRefine, Trans, default_factor_dtype,
    print_options)
from superlu_dist_tpu.utils.stats import Stats, SolveReport, RungRecord
from superlu_dist_tpu.utils.errors import (
    SuperLUError, SingularMatrixError, NumericBreakdownError,
    PatternMismatchError, RefactorRollbackError)
from superlu_dist_tpu.rowperm.equil import gsequ, laqgs
from superlu_dist_tpu.rowperm.matching import (
    maximum_product_matching, approximate_weight_matching)
from superlu_dist_tpu.ordering.dispatch import get_perm_c
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize, SymbolicFact
from superlu_dist_tpu.numeric.plan import build_plan, FactorPlan
from superlu_dist_tpu.numeric.factor import numeric_factorize, NumericFactorization
from superlu_dist_tpu.solve.trisolve import lu_solve, lu_solve_trans
from superlu_dist_tpu.refine.ir import iterative_refinement
from superlu_dist_tpu.utils import tols


@dataclasses.dataclass
class LUFactorization:
    """Persistent factorization handle — the {ScalePermstruct, LUstruct,
    SOLVEstruct} bundle of the reference API (superlu_ddefs.h:76-82,186-228)."""

    n: int
    options: Options
    equed: str
    dr: np.ndarray            # equilibration row scaling (or ones)
    dc: np.ndarray
    r1: np.ndarray            # matching scalings (or ones)
    c1: np.ndarray
    row_order: np.ndarray     # ρ: position j <- original row ρ[j]
    col_order: np.ndarray     # fill-reducing order fed to symbolic
    sf: SymbolicFact = None
    plan: FactorPlan = None
    numeric: NumericFactorization = None
    anorm: float = 0.0
    a: SparseCSR = None       # original matrix (for refinement SpMV)
    berrs: list = None        # backward errors of the last refinement
    a_sym_indptr: np.ndarray = None    # symmetrized pattern the symbolic
    a_sym_indices: np.ndarray = None   # factorization was built on
    dev_spmv: object = None            # cached DeviceSpMV per (trans,
                                       # dtype) — pdgsmv_init discipline
    dev_solver: object = None          # lazy DeviceSolver (SolveInitialized
                                       # analog, pdgssvx.c:1330-1337)
    solve_path: str = "auto"           # "auto" | "host" | "device"; "auto"
                                       # resolves on the first solve from
                                       # the regime (backend, mesh, factor
                                       # residency) and records the path
                                       # taken — a device-solve failure
                                       # raises, it never swaps to host
    mesh: object = None                # the grid mesh the factors are
                                       # sharded over (None off-grid).  When
                                       # it spans multiple PROCESSES the
                                       # solve must run collectively on it —
                                       # no process can pull the whole
                                       # factor (pdgstrs over the process
                                       # grid, SRC/pdgstrs.c:838)
    pattern_digest: str = None         # identity latch for the refactor
    plan_fp: str = None                # pipeline: sha256 of the symmetrized
                                       # permuted pattern + the plan
                                       # fingerprint, latched lazily on
                                       # first refactor (persist/serial.py
                                       # computes both; bundles record the
                                       # pattern digest in their meta)

    def identity(self) -> tuple:
        """Latch and return ``(pattern_digest, plan_fingerprint)`` — the
        refactor pipeline's identity discipline: a values-only refactor
        reuses symbolic + plan + compiled programs by OBJECT identity,
        so the handle carries a durable fingerprint of both and drift
        raises :class:`PatternMismatchError` instead of silently
        re-running symbolic."""
        from superlu_dist_tpu.persist.serial import (
            pattern_digest, plan_fingerprint)
        if self.pattern_digest is None and self.a_sym_indptr is not None:
            self.pattern_digest = pattern_digest(self.a_sym_indptr,
                                                 self.a_sym_indices)
        if self.plan_fp is None and self.plan is not None:
            self.plan_fp = plan_fingerprint(self.plan)
        return self.pattern_digest, self.plan_fp

    # -- combined transforms --------------------------------------------------
    @property
    def R(self):
        return self.r1 * self.dr

    @property
    def C(self):
        return self.dc * self.c1

    @property
    def sigma(self):
        """Composite row order: M rows <- original rows sigma[k]."""
        return self.row_order[self.sf.perm]

    def solve_factored(self, b: np.ndarray) -> np.ndarray:
        """Solve A·x = b through the factored M (no refinement).

        On an accelerator backend the triangular solves run device-side
        (solve/device.py, the pdgstrs analog) so the factors never cross
        the host boundary; on CPU the host supernodal solve is used (f64,
        which also serves the refinement's correction solves)."""
        if not self.numeric.finite:
            raise SingularMatrixError(self.numeric.info_col)
        b = np.asarray(b)
        d = b * (self.R[:, None] if b.ndim > 1 else self.R)
        d = d[self.sigma]
        z_hat = self._solve_permuted(d)
        z = np.empty_like(z_hat)
        z[self.sf.perm] = z_hat
        return z * (self.C[:, None] if b.ndim > 1 else self.C)

    def solve_factored_trans(self, b: np.ndarray,
                             conj: bool = False) -> np.ndarray:
        """Solve Aᵀ·x = b (or Aᴴ·x with conj) through the same factors.

        The reference's trans_t path (superlu_defs.h:628-657): with
        M = P_σ·diag(R)·A·diag(C)·P_πᵀ the transpose system becomes
        Mᵀ·(P_σ (x⊘R)) = P_π (C ⊙ b) — same transforms, mirrored order,
        solved via Uᵀ then Lᵀ sweeps (solve/trisolve.lu_solve_trans)."""
        if not self.numeric.finite:
            raise SingularMatrixError(self.numeric.info_col)
        b = np.asarray(b)
        C = self.C[:, None] if b.ndim > 1 else self.C
        R = self.R[:, None] if b.ndim > 1 else self.R
        d = (b * C)[self.sf.perm]
        w_hat = self._solve_permuted_trans(d, conj)
        w = np.empty_like(w_hat)
        w[self.sigma] = w_hat
        return w * R

    def _solve_permuted_trans(self, d: np.ndarray, conj: bool) -> np.ndarray:
        return self._dispatch_solve(
            lambda s: s.solve_trans(d, conj=conj),
            lambda: lu_solve_trans(self.numeric, d, conj=conj))

    def _dispatch_solve(self, device_call, host_call):
        """Shared device-vs-host solve dispatch (one copy — the plain and
        transpose paths must never drift)."""
        import jax
        # a mesh spanning multiple processes means no process holds the
        # whole factor: the solve MUST run collectively on the mesh (and
        # a host fallback is impossible — it would read non-addressable
        # shards), exactly like the reference's pdgstrs event loop over
        # the process grid (SRC/pdgstrs.c:838)
        multiproc = self.mesh is not None and jax.process_count() > 1
        use_device = (multiproc
                      or self.solve_path == "device"
                      or (self.solve_path == "auto"
                          and jax.default_backend() != "cpu"
                          # offloaded (host-resident) factors solve on the
                          # host — re-uploading them each solve would cost
                          # more than the device solve saves
                          and not self.numeric.on_host))
        # a SINGLE-process mesh routes to the shard_map SPMD tier
        # (parallel/spmd.SpmdSolver): the whole fwd+bwd sweep is ONE
        # compiled program per nrhs bucket, bitwise-identical to the
        # local DeviceSolver
        spmd = False
        if (self.mesh is not None and not multiproc
                and self.solve_path != "host"
                and not self.numeric.on_host):
            from superlu_dist_tpu.parallel.spmd import spmd_mode
            spmd = spmd_mode()
            use_device = use_device or spmd
        if self.solve_path == "auto":
            # backend, mesh and factor residency are fixed for a handle,
            # so the regime's choice is recorded once
            self.solve_path = "device" if use_device else "host"
        if not use_device:
            return host_call()
        if self.dev_solver is None:
            if spmd:
                from superlu_dist_tpu.parallel.spmd import SpmdSolver
                self.dev_solver = SpmdSolver(
                    self.numeric, self.mesh,
                    schedule=self.options.solve_schedule,
                    window=self.options.solve_window,
                    align=self.options.solve_align,
                    gemm_prec=getattr(self.options, "gemm_prec", None))
            else:
                from superlu_dist_tpu.solve.device import DeviceSolver
                # multiproc: streamed sweeps (fused=False) — the
                # whole-sweep programs at n≈1e5 hit the same compile wall
                # as the fused factor executor (see factor.get_executor's
                # auto rule)
                self.dev_solver = DeviceSolver(
                    self.numeric, diag_inv=self.options.diag_inv,
                    mesh=self.mesh if multiproc else None,
                    fused=False if multiproc else "auto",
                    schedule=self.options.solve_schedule,
                    window=self.options.solve_window,
                    align=self.options.solve_align,
                    gemm_prec=getattr(self.options, "gemm_prec", None))
        return device_call(self.dev_solver)

    def _solve_permuted(self, d: np.ndarray) -> np.ndarray:
        return self._dispatch_solve(lambda s: s.solve(d),
                                    lambda: lu_solve(self.numeric, d))


def analyze(options: Options, a: SparseCSR,
            lu: LUFactorization | None = None,
            stats: Stats | None = None):
    """The host analysis phases only: EQUIL → ROWPERM → COLPERM →
    SYMBFACT → DIST/plan (pdgssvx.c:647-1166 before pdgstrf).

    Returns ``(lu, bvals, stats)``: `lu` is an LUFactorization skeleton
    (numeric=None) carrying every transform plus the symbolic/plan, and
    `bvals` the structurally-permuted matrix values ready for
    factorize_numeric.  The split exists so the distributed-factors tier
    can run the analysis ONCE (on root) and broadcast the skeleton —
    O(nnz) transfer instead of O(nnz) redundant work and memory on every
    rank, the wall the reference's symbfact_dist was built to break
    (SRC/psymbfact.c:140,228-242).
    """
    if stats is None:
        stats = Stats()
    n = a.n_rows
    if a.n_cols != n:
        raise SuperLUError("A must be square")
    fact = options.fact

    reuse_rowperm = fact == Fact.SamePattern_SameRowPerm and lu is not None
    reuse_colperm = fact in (Fact.SamePattern, Fact.SamePattern_SameRowPerm) \
        and lu is not None
    if reuse_colperm and lu.sf is not None and lu.sf.value_perm is None:
        # a panalyze (ParSymbFact) skeleton assembles values directly and
        # records no value-gather map; the reuse tiers need one
        raise SuperLUError(
            "Fact reuse tiers require a serial-analysis skeleton; this one "
            "came from the distributed analysis (parallel/panalysis.py) — "
            "re-analyze with Fact=DOFACT")
    # Symbolic/plan reuse tiers.  Our symbolic runs on the row-permuted
    # pattern, so reuse is sound iff the row permutation is unchanged:
    # always true under SamePattern_SameRowPerm, and detected dynamically
    # under plain SamePattern after the fresh matching below (the common
    # time-stepping case — values drift, MC64 returns the same matching).
    # The reference's own plain-SamePattern tier likewise re-runs symbfact
    # (the pdgssvx.c:1034 gate skips it only for SamePattern_SameRowPerm)
    # and reuses perm_c + etree; detecting the equal-row-perm case reuses
    # strictly more than the reference whenever it fires.
    reuse_symbolic = reuse_rowperm

    # ---- EQUIL (pdgssvx.c:647-760) -----------------------------------------
    with stats.timer("EQUIL"):
        if reuse_rowperm:
            dr, dc, equed = lu.dr, lu.dc, lu.equed
            a1 = a.row_scale(dr).col_scale(dc) if equed != "N" else a
        elif options.equil:
            r, c, rowcnd, colcnd, amax = gsequ(a)
            a1, equed = laqgs(a, r, c, rowcnd, colcnd, amax)
            dr = r if equed in ("R", "B") else np.ones(n)
            dc = c if equed in ("C", "B") else np.ones(n)
        else:
            a1, equed = a, "N"
            dr = dc = np.ones(n)

    # ---- ROWPERM (pdgssvx.c:793-937) ---------------------------------------
    with stats.timer("ROWPERM"):
        if reuse_rowperm:
            row_order, r1, c1 = lu.row_order, lu.r1, lu.c1
            a2 = a1.row_scale(r1).col_scale(c1).permute(perm_r=row_order)
        elif options.row_perm == RowPerm.LargeDiag_MC64:
            row_order, r1, c1 = maximum_product_matching(a1)
            a2 = a1.row_scale(r1).col_scale(c1).permute(perm_r=row_order)
        elif options.row_perm == RowPerm.LargeDiag_AWPM:
            row_order = approximate_weight_matching(a1)
            r1 = c1 = np.ones(n)
            a2 = a1.permute(perm_r=row_order)
        elif options.row_perm == RowPerm.MY_PERMR:
            row_order = np.asarray(options.user_perm_r, dtype=np.int64)
            r1 = c1 = np.ones(n)
            a2 = a1.permute(perm_r=row_order)
        else:
            row_order = np.arange(n, dtype=np.int64)
            r1 = c1 = np.ones(n)
            a2 = a1

    if reuse_colperm and not reuse_symbolic and lu.sf is not None \
            and np.array_equal(row_order, lu.row_order):
        # plain SamePattern, and the fresh matching reproduced the prior
        # row order: the permuted pattern is unchanged, so the symbolic
        # and plan carry over (verified structurally by the DIST check
        # below) — SYMBFACT+DIST drop to ~0 while ROWPERM re-ran
        reuse_symbolic = True

    anorm = a2.norm_max()
    sym = symmetrize_pattern(a2)

    # ---- COLPERM (pdgssvx.c:958-1031) --------------------------------------
    with stats.timer("COLPERM"):
        if reuse_colperm:
            col_order = lu.col_order
        else:
            col_order = get_perm_c(options, a2, sym)

    # ---- ETREE + SYMBFACT (pdgssvx.c:1034-1118) ----------------------------
    et0 = stats.utime["ETREE"]
    with stats.timer("SYMBFACT"):
        if reuse_symbolic:
            sf = lu.sf
        else:
            sf = symbolic_factorize(sym, col_order, relax=options.relax,
                                    max_supernode=options.max_supernode,
                                    stats=stats, amalg_tol=options.amalg_tol)
    # phases are disjoint like the reference's PhaseType: the etree part
    # timed inside symbolic_factorize is carved out of SYMBFACT
    stats.utime["SYMBFACT"] -= stats.utime["ETREE"] - et0

    # ---- DIST / plan (pdgssvx.c:1132-1166) ---------------------------------
    with stats.timer("DIST"):
        if reuse_symbolic:
            plan = lu.plan
        else:
            plan = build_plan(sf, min_bucket=options.min_bucket,
                              growth=options.bucket_growth,
                              schedule=options.schedule,
                              window=options.sched_window,
                              align=options.sched_align,
                              closed=options.bucket_closed)
        pattern_mismatch = sym.nnz != len(sf.value_perm)
        if not pattern_mismatch and reuse_symbolic:
            # nnz equality is not enough: a moved entry with equal count
            # would gather values into wrong structural slots silently
            pattern_mismatch = not (
                np.array_equal(sym.indptr, lu.a_sym_indptr)
                and np.array_equal(sym.indices, lu.a_sym_indices))
        if pattern_mismatch:
            raise SuperLUError(
                f"Fact={fact.name} reuse requires the same sparsity pattern "
                f"as the factorization being reused")
        bvals = sym.data[sf.value_perm]

    lu = LUFactorization(n=n, options=options, equed=equed, dr=dr, dc=dc,
                         r1=r1, c1=c1, row_order=row_order,
                         col_order=col_order, sf=sf, plan=plan,
                         numeric=None, anorm=anorm, a=a,
                         a_sym_indptr=sym.indptr, a_sym_indices=sym.indices)
    return lu, bvals, stats


def factorize_numeric(lu: LUFactorization, bvals: np.ndarray,
                      stats: Stats | None = None, grid=None,
                      resume_from: str | None = None,
                      deadline_comm=None) -> int:
    """Numeric factorization (pdgssvx.c:1176 → pdgstrf, SRC/pdgstrf.c:243)
    on an analyzed skeleton from `analyze`.

    With `grid`, the factorization runs sharded over the grid's mesh —
    when that mesh spans multiple processes this is an SPMD collective
    every rank must enter with the SAME skeleton and values (the
    distributed-factors tier broadcasts them first).  Fills lu.numeric in
    place; returns info (0, or 1-based first zero-pivot column).

    Crash consistency (docs/RELIABILITY.md): ``Options.ckpt_every`` arms
    mid-factor frontier checkpoints; ``resume_from`` restarts from a
    durable checkpoint instead of from scratch (recorded on
    ``stats.resume`` and as a SolveReport rung by the solve tail);
    ``Options.deadline_s`` bounds the factor loop, with ``deadline_comm``
    (a TreeComm on the distributed tier) making expiry a collective
    decision so cancellation can never strand a rank in a collective."""
    if stats is None:
        stats = Stats()
    options = lu.options
    plan = lu.plan
    from superlu_dist_tpu.numeric.stream import RETRACE_SENTINEL
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    retr0 = RETRACE_SENTINEL.total
    comp0 = COMPILE_STATS.marker()
    dtype = options.factor_dtype or default_factor_dtype()
    if np.issubdtype(np.asarray(bvals).dtype, np.complexfloating):
        dtype = {"float32": "complex64", "float64": "complex128"}.get(str(dtype), dtype)
    deadline = None
    if options.deadline_s:
        from superlu_dist_tpu.utils.deadline import Deadline
        from superlu_dist_tpu.utils.options import env_int
        deadline = Deadline(options.deadline_s, comm=deadline_comm,
                            poll_every=env_int("SLU_TPU_DEADLINE_POLL"))
    # checkpoints need a single-process pool boundary; the multi-process
    # mesh shards it, so only the deadline travels onto the grid tier
    want_ckpt = options.ckpt_every > 0 and grid is None
    with stats.timer("FACT"):
        if str(dtype) == "df64":
            if resume_from:
                raise SuperLUError(
                    "resume_from is not supported for df64 factorization "
                    "(its factor loop has no checkpoint boundaries yet)")
            # emulated-double factorization for f32-only hardware (true
            # ~2^-48 factors; SURVEY.md §7 hard-part 1), real AND complex
            # (zdf64, the pzgstrf twin — SRC/pzgstrf.c:243); host
            # f64/c128 factors come back, so the standard solve path
            # applies
            from superlu_dist_tpu.numeric.df64_factor import (
                df64_numeric_factorize)
            numeric = df64_numeric_factorize(
                plan, bvals, lu.anorm,
                replace_tiny=options.replace_tiny_pivot,
                mesh=grid.mesh if grid is not None else None,
                pool_partition=options.pool_partition,
                check_finite=options.recovery.sentinels)
        else:
            numeric = numeric_factorize(
                plan, bvals, lu.anorm, dtype=dtype,
                replace_tiny=options.replace_tiny_pivot,
                executor=getattr(options, "executor", "auto") or "auto",
                mesh=grid.mesh if grid is not None else None,
                pool_partition=options.pool_partition,
                check_finite=options.recovery.sentinels,
                ckpt_dir=(options.ckpt_dir or None) if want_ckpt else None,
                ckpt_every=options.ckpt_every if want_ckpt else 0,
                resume_from=resume_from,
                deadline=deadline,
                gemm_prec=getattr(options, "gemm_prec", None))
        for lp, up in numeric.fronts:
            if hasattr(lp, "block_until_ready"):
                lp.block_until_ready()
                up.block_until_ready()
    stats.ops["FACT"] += plan.flops
    stats.tiny_pivots += numeric.tiny_pivots
    # dispatch-schedule telemetry (numeric/plan.py): surfaced on the
    # same Stats the PStatPrint-analog report prints; bytes_moved uses
    # the factor dtype's real itemsize (df64 = paired f64 components)
    try:
        _isz = np.dtype(dtype).itemsize
    except TypeError:
        _isz = 16
    stats.sched = plan.schedule_stats(itemsize=_isz)
    # retrace sentinel (runtime SLU106): unexpected recompiles during
    # THIS factorization, surfaced on the same Stats the report prints
    stats.retraces += RETRACE_SENTINEL.total - retr0
    # compile census (obs/compilestats.py): the jit builds THIS
    # factorization paid, as a stats.compile block in the same report
    stats.compile = COMPILE_STATS.block(since=comp0)
    from superlu_dist_tpu.obs.metrics import get_metrics
    m = get_metrics()
    if m.enabled:
        sched = stats.sched
        m.inc("slu_factorizations_total", 1.0,
              schedule=sched.get("schedule", "?"))
        # throughput-ladder telemetry: which GEMM tier the factors ran
        # at (the escalation rung increments this again per refactor)
        m.inc("slu_gemm_precision_total", 1.0,
              tier=getattr(numeric, "gemm_prec", "highest"))
        m.set("slu_schedule_groups", sched.get("n_groups", 0))
        m.set("slu_schedule_occupancy", sched.get("occupancy", 0.0))
        m.set("slu_schedule_critical_path", sched.get("critical_path", 0))
        m.inc("slu_compile_builds_total",
              float(stats.compile.get("builds", 0)))
        m.inc("slu_compile_seconds_total",
              float(stats.compile.get("seconds", 0.0)))
    # memory observability (dQuerySpace_dist analog, SRC/dmemory_dist.c:73)
    from superlu_dist_tpu.numeric.factor import query_space
    space = query_space(numeric)
    stats.observe_memory(space["total_bytes"])
    stats.for_lu_bytes = space["for_lu_bytes"]
    stats.pool_bytes = space["pool_bytes"]

    if getattr(numeric, "resumed_groups", 0):
        # resume telemetry: surfaced in the Stats report and recorded as
        # an escalation-ladder rung on the SolveReport by the solve tail
        stats.resume = {"groups": int(numeric.resumed_groups),
                        "of": len(plan.groups),
                        "path": str(resume_from)}
    lu.numeric = numeric
    lu.mesh = grid.mesh if grid is not None else None
    # invalidate solve-side caches from any prior factorization the
    # skeleton was reused from
    lu.dev_solver = None
    if not numeric.finite:
        # exactly singular U and no tiny-pivot replacement: info is the
        # 1-based first zero-pivot column, like the reference's Allreduce-MIN
        # of the first i with U(i,i)==0 (pdgstrf.c:1920-1924)
        return numeric.info_col + 1
    return 0


# per-process refactor counter: the chaos harness's `kill_refactor@step=K`
# spec is scoped to the Kth refactor of the victim process (0-based)
_REFACTOR_SEQ = [0]


def refactor(lu: LUFactorization, new_values,
             stats: Stats | None = None, canary_b: np.ndarray = None,
             berr_max: float | None = None):
    """Values-only refactorization — the middle rung of the Fact ladder
    (SamePattern_SameRowPerm economics as a first-class crash-consistent
    verb, ROADMAP item 2).

    ``new_values`` is either a :class:`SparseCSR` with the SAME sparsity
    pattern the handle was analyzed on, or a raw data array replacing
    ``lu.a.data`` entry-for-entry.  The symbolic structure, FactorPlan,
    bucket set AND compiled programs are reused by object identity —
    zero symbolic seconds and zero fresh-compile seconds by construction
    (the executor cache on ``plan._factor_fns`` is keyed by the plan
    object; ``stats.compile['fresh_seconds']`` proves it per call).

    Identity discipline: the pattern digest + plan fingerprint are
    latched on the handle (:meth:`LUFactorization.identity`); a matrix
    whose symmetrized permuted pattern drifts from the latch raises a
    structured :class:`PatternMismatchError` instead of silently
    re-running symbolic.

    Commit protocol (adopt-only-on-improvement): the numeric
    factorization runs against a SHADOW copy of the handle — in-flight
    solves keep the previous panels — and is adopted onto ``lu`` only
    after (a) the factorization finished finite (breakdown sentinels /
    singularity reject at ``stage='factor'``), and (b) the BERR canary
    passed: one un-refined solve of ``canary_b`` (default: ones) must
    come back finite, and — when a gate is armed via ``berr_max`` /
    ``SLU_TPU_REFACTOR_BERR_MAX`` — with componentwise backward error
    at or below it.  A canary miss at a reduced GEMM tier first climbs
    the PR 15 escalation ladder (``SLU_TPU_REFACTOR_ESCALATE``) one
    tier per rung; if the ladder tops out the refactor raises
    :class:`RefactorRollbackError` and ``lu`` is untouched.  An
    interrupted refactor (kill -9, deadline, poisoned values — the
    ``kill_refactor``/``poison_values`` chaos specs) always leaves the
    previous consistent handle serving.

    Returns ``stats``; on success ``lu`` serves the new factors (its
    ``numeric``/``a``/``anorm`` swapped, device caches invalidated)."""
    if stats is None:
        stats = Stats()
    step = _REFACTOR_SEQ[0]
    _REFACTOR_SEQ[0] += 1
    from superlu_dist_tpu.obs.metrics import get_metrics
    m = get_metrics()
    if m.enabled:
        m.inc("slu_refactor_total", 1.0)

    if lu.sf is None or lu.plan is None:
        raise SuperLUError(
            "refactor requires an analyzed handle (lu.sf/lu.plan is "
            "None — run analyze/gssvx first)")
    if lu.sf.value_perm is None:
        raise SuperLUError(
            "refactor requires a serial-analysis skeleton; this one came "
            "from the distributed analysis (parallel/panalysis.py) — "
            "re-analyze with Fact=DOFACT")
    if lu.a_sym_indptr is None:
        raise SuperLUError(
            "refactor requires the handle's analyzed pattern "
            "(a_sym_indptr is None — e.g. a hand-built skeleton); "
            "re-analyze with Fact=DOFACT")
    expected_digest, _ = lu.identity()

    # ---- new-values intake + pattern identity check ------------------------
    a_new = new_values
    if not hasattr(a_new, "indptr"):
        vals = np.asarray(new_values)
        if lu.a is None:
            raise SuperLUError(
                "refactor from a raw value array needs the handle's "
                "matrix for its pattern (lu.a is None — pass a SparseCSR "
                "instead)")
        if vals.ndim != 1 or vals.shape[0] != lu.a.nnz:
            raise PatternMismatchError(
                f"value array has {vals.shape} entries, the handle's "
                f"pattern has {lu.a.nnz} nonzeros",
                expected_digest=expected_digest, n=lu.n, nnz=lu.a.nnz)
        a_new = SparseCSR(lu.a.n_rows, lu.a.n_cols, lu.a.indptr,
                          lu.a.indices, vals)
    if a_new.n_rows != lu.n or a_new.n_cols != lu.n:
        raise PatternMismatchError(
            f"matrix is {a_new.n_rows}x{a_new.n_cols}, the handle was "
            f"analyzed at n={lu.n}", expected_digest=expected_digest,
            n=lu.n)
    # apply the handle's stored transforms to the new matrix (the
    # SamePattern_SameRowPerm recipe: reuse scalings + row order), then
    # verify the symmetrized permuted pattern is EXACTLY the analyzed one
    # — nnz equality is not enough, a moved entry with equal count would
    # gather values into wrong structural slots silently
    a1 = (a_new.row_scale(lu.dr).col_scale(lu.dc)
          if lu.equed != "N" else a_new)
    a2 = a1.row_scale(lu.r1).col_scale(lu.c1).permute(perm_r=lu.row_order)
    sym = symmetrize_pattern(a2)
    if sym.nnz != len(lu.sf.value_perm) or not (
            np.array_equal(sym.indptr, lu.a_sym_indptr)
            and np.array_equal(sym.indices, lu.a_sym_indices)):
        from superlu_dist_tpu.persist.serial import pattern_digest
        raise PatternMismatchError(
            "the matrix's symmetrized permuted pattern differs from the "
            "one the handle's symbolic structure was built on",
            expected_digest=expected_digest,
            got_digest=pattern_digest(sym.indptr, sym.indices),
            n=lu.n, nnz=sym.nnz)
    bvals = sym.data[lu.sf.value_perm]
    anorm = a2.norm_max()

    # ---- chaos hooks (testing/chaos.py, consulted once per refactor) -------
    from superlu_dist_tpu.testing.chaos import get_refactor_chaos
    monkey = get_refactor_chaos()
    if monkey is not None:
        bvals = monkey.poison_refactor_values(lu.plan, bvals)
        if monkey.refactor_kill_due(step):
            # mid-refactor: the new values are staged, nothing adopted —
            # crash consistency demands the previous handle (and any
            # bundle on disk) survive this untouched
            monkey.kill_now()

    # ---- shadow numeric factorization (adopt-only-on-improvement) ----------
    from superlu_dist_tpu.refine.ir import request_berrs
    from superlu_dist_tpu.ops.dense import next_gemm_precision
    from superlu_dist_tpu.utils.options import env_flag, env_float
    if berr_max is None:
        berr_max = env_float("SLU_TPU_REFACTOR_BERR_MAX")
    escalate = env_flag("SLU_TPU_REFACTOR_ESCALATE")
    if canary_b is None:
        canary_b = np.ones(lu.n, dtype=np.asarray(a_new.data).dtype)

    def rollback(stage, cause="", berr=-1.0):
        if m.enabled:
            m.inc("slu_refactor_rollbacks_total", 1.0, stage=stage)
        return RefactorRollbackError(
            "handle", stage=stage, cause=cause, berr=berr,
            berr_target=berr_max if berr_max > 0 else -1.0)

    tier = None                    # None = the handle's configured tier
    rungs = max(int(lu.options.recovery.max_rungs), 1)
    shadow = None
    for rung in range(rungs):
        opts = (lu.options if tier is None
                else dataclasses.replace(lu.options, gemm_prec=tier))
        shadow = dataclasses.replace(
            lu, numeric=None, dev_solver=None, dev_spmv=None, berrs=None,
            a=a_new, anorm=anorm, options=opts)
        try:
            info = factorize_numeric(shadow, bvals, stats)
        except SuperLUError as e:
            raise rollback("factor", f"{type(e).__name__}: {e}") from e
        if info != 0:
            raise rollback("factor", f"singular: info={info}")
        # ---- BERR canary (refine/ir.py — one solve + one SpMV pair) ----
        try:
            x = shadow.solve_factored(canary_b)
            finite = bool(np.all(np.isfinite(np.asarray(x))))
            berr = (float(request_berrs(a_new, canary_b, x).max())
                    if finite else float("inf"))
        except SuperLUError as e:
            raise rollback("canary", f"{type(e).__name__}: {e}") from e
        if finite and (berr_max <= 0 or berr <= berr_max):
            break
        nxt = next_gemm_precision(
            getattr(shadow.numeric, "gemm_prec", "highest"))
        if not escalate or nxt is None or rung == rungs - 1:
            raise rollback(
                "canary",
                "non-finite canary X" if not finite else
                "canary backward error above the gate", berr=berr)
        # the PR 15 escalation machinery: retry the shadow one GEMM
        # tier up — same plan, same programs at that tier's cache slot
        tier = nxt
        if m.enabled:
            m.inc("slu_recovery_rungs_total", 1.0,
                  rung="refactor-gemm-precision", improved="pending")

    # ---- atomic adoption ---------------------------------------------------
    # single-field rebinds onto the live handle: a concurrent solve holds
    # either the complete old numeric or the complete new one (the serve
    # tier additionally serializes via its swap lock)
    lu.numeric = shadow.numeric
    lu.mesh = shadow.mesh
    lu.dev_solver = None
    lu.dev_spmv = None
    lu.berrs = None
    lu.a = a_new
    lu.anorm = anorm
    if tier is not None:
        lu.options = shadow.options
    if m.enabled:
        m.inc("slu_refactor_adopted_total", 1.0)
    from superlu_dist_tpu.obs.flightrec import get_flightrec
    get_flightrec().event(
        "refactor-adopted", cat="refactor", step=step,
        pattern=expected_digest[:12] if expected_digest else "",
        fresh_compile_s=float(stats.compile.get("fresh_seconds", 0.0))
        if stats.compile else 0.0)
    return stats


def gssvx(options: Options, a: SparseCSR, b: np.ndarray,
          lu: LUFactorization | None = None, stats: Stats | None = None,
          grid=None, resume_from: str | None = None):
    """Solve A·X = B.  Returns (x, lu, stats, info).

    info = 0 on success; > 0 mirrors the reference's singularity reporting
    via tiny-pivot counts in stats (with ReplaceTinyPivot the factorization
    always completes, pdgstrf2.c:218-232).

    `grid` is a parallel.grid.ProcessGrid (the reference passes gridinfo_t
    to pdgssvx): the numeric factorization and device solve then run
    sharded over the grid's mesh.

    `resume_from` names a factor checkpoint (persist/checkpoint.py —
    written by a prior run that died mid-factorization under
    Options.ckpt_every, a deadline, or SIGTERM): the analysis re-runs
    (cheap, deterministic), the checkpoint's plan fingerprint and value
    digest are verified against it, and the numeric factorization
    restarts from the durable frontier instead of from scratch — the
    factors come out bitwise-identical to an uninterrupted run.  The
    resume is recorded on stats.resume and as a 'resume-from-checkpoint'
    rung in the SolveReport ladder.
    """
    if stats is None:
        stats = Stats()
    if options.print_stat:
        print(print_options(options))
    ft = getattr(options, "ft", "abort") or "abort"
    if ft not in ("abort", "shrink", "respawn"):
        # fail the typo'd SLU_TPU_FT here, on every driver, instead of
        # silently aborting the first real rank failure
        raise SuperLUError(
            f"Options.ft must be abort|shrink|respawn, got {ft!r}")
    n = a.n_rows
    if a.n_cols != n:
        raise SuperLUError("A must be square")
    b = np.asarray(b)
    if b.shape[0] != n:
        raise SuperLUError("B leading dimension must match A")

    if options.fact == Fact.FACTORED:
        if lu is None or lu.numeric is None:
            raise SuperLUError("Fact=FACTORED requires a prior factorization")
        return _solve_and_refine(options, a, b, lu, stats)

    lu, bvals, stats = analyze(options, a, lu=lu, stats=stats)
    info = factorize_numeric(lu, bvals, stats, grid=grid,
                             resume_from=resume_from)
    if info != 0:
        return None, lu, stats, info
    return _solve_and_refine(options, a, b, lu, stats)


def gssvx_ABglobal(options: Options, a: SparseCSR, b: np.ndarray,
                   lu: LUFactorization | None = None,
                   stats: Stats | None = None):
    """pdgssvx_ABglobal analog (SRC/pdgssvx_ABglobal.c:472).

    The reference maintains two pipelines because its main driver takes a
    *distributed* NRformat_loc matrix while ABglobal takes a *replicated*
    one.  Here the host analysis always sees the global matrix (the
    distributed input path is gssvx_dist below), so ABglobal coincides
    with gssvx — kept as a named entry point for API parity.
    """
    return gssvx(options, a, b, lu=lu, stats=stats)


def gssvx_dist(options: Options, parts, b: np.ndarray,
               lu: LUFactorization | None = None,
               stats: Stats | None = None):
    """Solve from a distributed row-block matrix (the reference's primary
    pdgssvx signature: NRformat_loc input, SRC/pdgssvx.c:505).

    `parts` is a list of parallel.dist.DistributedCSR row blocks; they are
    assembled host-side (the dReDistribute_A role, SRC/pddistribute.c:61 —
    one gather instead of two all-to-alls, since the analysis is
    single-address-space) and solved with the standard pipeline.
    """
    from superlu_dist_tpu.parallel.dist import gather_rows
    return gssvx(options, gather_rows(parts), b, lu=lu, stats=stats)


def _adjoint_solver(lu: LUFactorization, trans, cplx: bool):
    """op⁻ᴴ through the stored factors (for the FERR estimator); None when
    the trans/complex combination has no clean adjoint through them."""
    if trans == Trans.NOTRANS:
        return lambda r: lu.solve_factored_trans(r, conj=cplx)
    if not cplx:
        return lu.solve_factored     # real: (Aᵀ)ᴴ = A
    return None


def _trans_solver(lu: LUFactorization, trans, a_dtype):
    """The op(A)⁻¹ apply matching options.trans, on an arbitrary handle."""
    if trans == Trans.NOTRANS:
        return lu.solve_factored
    conj = trans == Trans.CONJ and np.issubdtype(a_dtype,
                                                 np.complexfloating)
    return lambda rhs: lu.solve_factored_trans(rhs, conj=conj)


def _escalation_dtype(cur) -> str | None:
    """The next factor-precision tier above `cur`, or None at the top:
    f64/c128 on a CPU backend with x64, emulated-double df64 on f32-only
    hardware (numeric/df64_factor.py — true ~2^-48 factors)."""
    cur = str(cur)
    if cur in ("float64", "complex128") or "df64" in cur:
        return None
    import jax
    if jax.default_backend() == "cpu" and jax.config.read("jax_enable_x64"):
        return "float64"
    return "df64"


def _permuted_values(lu: LUFactorization):
    """Recompute analyze()'s structurally-permuted value array from the
    stored transforms (so an escalation rung can refactor on the SAME
    skeleton without redoing the analysis).  None when the skeleton cannot
    reproduce it — panalyze skeletons (no value-gather map), stripped
    handles, or pattern drift."""
    if lu.a is None or lu.sf is None or lu.sf.value_perm is None:
        return None
    a1 = (lu.a.row_scale(lu.dr).col_scale(lu.dc)
          if lu.equed != "N" else lu.a)
    a2 = a1.row_scale(lu.r1).col_scale(lu.c1).permute(perm_r=lu.row_order)
    sym = symmetrize_pattern(a2)
    if sym.nnz != len(lu.sf.value_perm):
        return None
    if (lu.a_sym_indptr is not None
            and not (np.array_equal(sym.indptr, lu.a_sym_indptr)
                     and np.array_equal(sym.indices, lu.a_sym_indices))):
        return None
    return sym.data[lu.sf.value_perm]


@contextlib.contextmanager
def _rung_span(report: SolveReport, name: str, **attrs):
    """The ``rung`` span of one escalation rung (its refactor, solver and
    refinement), closed with what the rung's record says: berr before and
    after, and whether the ladder adopted it (berr strictly improved)."""
    n0 = len(report.rungs)
    with get_tracer().span(name, cat="rung", **attrs) as sp:
        yield
        if len(report.rungs) > n0:
            r = report.rungs[-1]
            sp.set(berr_before=float(r.berr_before),
                   berr_after=float(r.berr_after),
                   adopted=bool(r.berr_after < r.berr_before))


def _escalate(options: Options, a: SparseCSR, op, b: np.ndarray,
              lu: LUFactorization, stats: Stats, trans, solve_fn,
              x: np.ndarray, residual_dtype, report: SolveReport,
              target: float):
    """The automatic escalation ladder (the ShyLU fallback-ladder shape:
    low-precision node solves wrapped in quality checks).  Runs when
    refinement stagnated above `target` or produced non-finite values:

      1. residual-precision — same factors, exact f64 residual;
      2. hiprec-factors     — refactor the SAME skeleton at the next
                              precision tier (f64 / df64) and redo the
                              correction solves through it;
      3. refactor-rescale   — full re-analysis with equilibration +
                              MC64 re-scaling/ordering forced on, at the
                              escalated precision.

    Every rung is recorded in report.rungs whether or not it helped; a
    rung's result is only ADOPTED when it strictly improved berr.
    Returns (x, lu_effective, solve_fn, residual_dtype)."""
    import time

    recovery = options.recovery
    rungs0 = len(report.rungs)
    cur_x = np.asarray(x)
    cur_berr = report.berr if report.berr is not None else float("inf")
    if not np.all(np.isfinite(cur_x)):
        cur_berr = float("inf")
    lu_eff = lu
    a_dtype = np.asarray(a.data).dtype

    def attempt(name, detail, solve2, res_dtype, start_x):
        """Run IR with `solve2` corrections; record; adopt on improvement.
        Returns True when the target is reached."""
        nonlocal cur_x, cur_berr, solve_fn, residual_dtype
        t0 = time.perf_counter()
        try:
            x0 = (start_x if np.all(np.isfinite(start_x))
                  else np.asarray(solve2(b)))
            x2, errs = iterative_refinement(op, b, x0, solve2,
                                            residual_dtype=res_dtype)
        except SuperLUError as e:
            report.rungs.append(RungRecord(
                name=name, detail=f"{detail}: {type(e).__name__}",
                berr_before=cur_berr,
                seconds=time.perf_counter() - t0))
            return False
        berr2 = errs[-1] if errs else float("inf")
        if not np.all(np.isfinite(np.asarray(x2))):
            berr2 = float("inf")
        report.rungs.append(RungRecord(
            name=name, detail=detail, berr_before=cur_berr,
            berr_after=berr2, seconds=time.perf_counter() - t0))
        report.berr_history.extend(errs)
        stats.refine_steps += len(errs)
        if berr2 < cur_berr:
            cur_x, cur_berr = np.asarray(x2), berr2
            solve_fn, residual_dtype = solve2, res_dtype
            report.berr = berr2
        return cur_berr <= target

    done = False
    # ---- rung 1: escalate residual precision --------------------------------
    # (SLU_SINGLE's f32 residual can't see below single eps; same factors,
    # exact residual is the cheapest repair)
    if (np.dtype(residual_dtype) != np.float64
            and len(report.rungs) < recovery.max_rungs):
        with _rung_span(report, "residual-precision", dtype="float64"):
            done = attempt("residual-precision", "float64 residual",
                           solve_fn, np.float64, cur_x)

    # ---- rung 1.5: gemm-precision ladder ------------------------------------
    # The throughput-ladder safety net (docs/PERFORMANCE.md): a reduced
    # GEMM tier (bf16 / the tensorfloat-analog default) that missed the
    # BERR gate refactors the SAME skeleton — same dtype, same scalings,
    # same plan — one tier up per rung until the gate passes or the
    # ladder tops out at "highest".  This is what makes the fast tier
    # safe to run default-on: delivered accuracy is gated, never assumed.
    from superlu_dist_tpu.ops.dense import next_gemm_precision
    tier = getattr(lu.numeric, "gemm_prec", "highest")
    while not done and len(report.rungs) < recovery.max_rungs:
        nxt = next_gemm_precision(tier)
        if nxt is None:
            break
        bvals = _permuted_values(lu)
        if bvals is None:
            break
        with _rung_span(report, "gemm-precision", tier=nxt):
            t0 = time.perf_counter()
            lu_prec = dataclasses.replace(
                lu, numeric=None, dev_solver=None, dev_spmv=None, berrs=None,
                options=dataclasses.replace(options, gemm_prec=nxt))
            try:
                info_p = factorize_numeric(lu_prec, bvals, stats)
            except SuperLUError as e:
                report.rungs.append(RungRecord(
                    name="gemm-precision", detail=f"{nxt}: {type(e).__name__}",
                    berr_before=cur_berr,
                    seconds=time.perf_counter() - t0))
                break
            if info_p != 0:
                report.rungs.append(RungRecord(
                    name="gemm-precision", detail=f"{nxt}: info={info_p}",
                    berr_before=cur_berr,
                    seconds=time.perf_counter() - t0))
                break
            solve_p = _trans_solver(lu_prec, trans, a_dtype)
            done = attempt("gemm-precision", nxt, solve_p, np.float64, cur_x)
        adopted = solve_fn is solve_p
        if adopted:                   # adopted: the answer now rests on
            lu_eff = lu_prec          # the higher-tier factors
        tier = nxt
        if not done and not adopted:
            # the tier step bought nothing: the GEMM precision is not
            # the binding error source (factor DTYPE usually is) —
            # leave the remaining rung budget to the dtype escalation
            break

    # ---- rung 2: higher-precision correction factors ------------------------
    esc = _escalation_dtype(lu.numeric.dtype)
    if (not done and esc is not None
            and len(report.rungs) < recovery.max_rungs):
        bvals = _permuted_values(lu)
        if bvals is not None:
            with _rung_span(report, "hiprec-factors", dtype=esc):
                # dtype escalation subsumes the gemm ladder: the hiprec
                # refactor always runs at the top GEMM tier
                lu_esc = dataclasses.replace(
                    lu, numeric=None, dev_solver=None, dev_spmv=None,
                    berrs=None,
                    options=dataclasses.replace(options, factor_dtype=esc,
                                                gemm_prec="highest"))
                try:
                    info2 = factorize_numeric(lu_esc, bvals, stats)
                except SuperLUError:
                    info2 = -1
                if info2 == 0:
                    solve2 = _trans_solver(lu_esc, trans, a_dtype)
                    done = attempt("hiprec-factors", esc, solve2,
                                   np.float64, cur_x)
                    if solve_fn is solve2:    # adopted: hand the caller the
                        lu_eff = lu_esc       # factors the answer rests on

    # ---- rung 3: refactor with re-scaling / re-ordering ---------------------
    # only when it would actually change something the first pass didn't do
    would_change = (not options.equil
                    or options.row_perm != RowPerm.LargeDiag_MC64
                    or not options.replace_tiny_pivot
                    or esc is not None)
    if not done and would_change and len(report.rungs) < recovery.max_rungs:
        with _rung_span(report, "refactor-rescale",
                        dtype=str(esc or options.factor_dtype)):
            t0 = time.perf_counter()
            opts3 = dataclasses.replace(
                options, fact=Fact.DOFACT, equil=True,
                row_perm=RowPerm.LargeDiag_MC64, replace_tiny_pivot=True,
                factor_dtype=esc if esc is not None else options.factor_dtype,
                gemm_prec="highest",        # the last rung gambles nothing
                iter_refine=IterRefine.SLU_DOUBLE, print_stat=False,
                user_perm_r=None,
                # no recursion, no mid-ladder raises: the ladder itself is
                # the consumer of this sub-solve's report
                recovery=dataclasses.replace(recovery, enabled=False,
                                             condest="never", sentinels=False))
            try:
                x3, lu3, stats3, info3 = gssvx(opts3, a, b)
            except SuperLUError as e:
                x3, lu3, stats3, info3 = None, None, None, -1
                err3 = type(e).__name__
            if info3 == 0 and x3 is not None:
                rep3 = stats3.solve_report
                berr3 = (rep3.berr if rep3 is not None
                         and rep3.berr is not None else float("inf"))
                if not np.all(np.isfinite(np.asarray(x3))):
                    berr3 = float("inf")
                report.rungs.append(RungRecord(
                    name="refactor-rescale", detail=str(opts3.factor_dtype),
                    berr_before=cur_berr, berr_after=berr3,
                    seconds=time.perf_counter() - t0))
                if rep3 is not None:
                    report.berr_history.extend(rep3.berr_history)
                if berr3 < cur_berr:
                    cur_x, cur_berr, lu_eff = np.asarray(x3), berr3, lu3
                    solve_fn = _trans_solver(lu3, trans, a_dtype)
                    residual_dtype = np.float64
                    report.berr = berr3
                    report.tiny_pivots = rep3.tiny_pivots if rep3 else 0
            else:
                report.rungs.append(RungRecord(
                    name="refactor-rescale",
                    detail=f"failed: info={info3}"
                           + (f" ({err3})" if info3 == -1 else ""),
                    berr_before=cur_berr,
                    seconds=time.perf_counter() - t0))

    # the tier/dtype the delivered answer actually rests on (lu_eff may
    # be an escalated handle from any rung above)
    if lu_eff.numeric is not None:
        report.gemm_precision = getattr(lu_eff.numeric, "gemm_prec",
                                        report.gemm_precision)
        report.factor_dtype = str(lu_eff.numeric.dtype)

    # serving metrics: one rung-transition counter per ladder action
    # this solve took (labeled by rung and whether it was adopted)
    from superlu_dist_tpu.obs.metrics import get_metrics
    m = get_metrics()
    if m.enabled:
        for r in report.rungs[rungs0:]:
            m.inc("slu_recovery_rungs_total", 1.0, rung=r.name,
                  improved=str(r.berr_after < r.berr_before).lower())
            m.observe("slu_recovery_rung_seconds", r.seconds, rung=r.name)
    return cur_x, lu_eff, solve_fn, residual_dtype


def _solve_and_refine(options: Options, a: SparseCSR, b: np.ndarray,
                      lu: LUFactorization, stats: Stats):
    t_req0 = time.perf_counter()
    n = a.n_rows
    # trans dispatch (reference trans_t, superlu_defs.h:628-657): TRANS and
    # CONJ solve AᵀX=B / AᴴX=B through the same factors; refinement then
    # needs the transposed operator for its residual SpMV
    trans = options.trans
    if trans == Trans.NOTRANS:
        solve_fn, op = lu.solve_factored, a
    else:
        conj = trans == Trans.CONJ and np.issubdtype(
            a.data.dtype, np.complexfloating)
        solve_fn = lambda rhs: lu.solve_factored_trans(rhs, conj=conj)  # noqa: E731
        op = a.transpose()
        if conj:
            op = SparseCSR(op.n_rows, op.n_cols, op.indptr, op.indices,
                           op.data.conj())
    with stats.timer("SOLVE"):
        x = solve_fn(b)
    nrhs = 1 if b.ndim == 1 else b.shape[1]
    stats.ops["SOLVE"] += 4.0 * lu.sf.nnz_L * nrhs  # fwd+back L,U sweeps

    info = 0
    report = SolveReport(factor_dtype=str(lu.numeric.dtype),
                         tiny_pivots=lu.numeric.tiny_pivots,
                         gemm_precision=getattr(lu.numeric, "gemm_prec",
                                                "highest"))
    if stats.resume:
        # a factorization resumed from a durable checkpoint is a ladder
        # action in its own right: the report must show the answer rests
        # partly on restored state (and where that state came from)
        report.rungs.append(RungRecord(
            name="resume-from-checkpoint",
            detail=f"{stats.resume['groups']}/{stats.resume['of']} groups "
                   f"from {stats.resume['path']}"))
    stats.solve_report = report
    recovery = options.recovery
    if options.iter_refine != IterRefine.NOREFINE:
        # SLU_SINGLE rounds the residual/correction to f32 (refinement
        # stops at single eps); SLU_DOUBLE uses options.ir_dtype (f64
        # default) — the reference's IterRefine tiers
        residual_dtype = (np.float32
                          if options.iter_refine == IterRefine.SLU_SINGLE
                          else np.dtype(options.ir_dtype))
        # device-resident residual SpMV (pdgsmv analog, SRC/pdgsmv.c:234)
        # when an accelerator is present, A is big enough for the upload
        # to pay for itself and x64 is on (a 64-bit residual needs it);
        # host numpy otherwise.  A device SpMV that fails raises.
        ir_op = op
        import jax
        stats.ir_residual = "host"
        if (jax.default_backend() != "cpu"
                and op.nnz >= 100_000 and not lu.numeric.on_host
                and jax.config.read("jax_enable_x64")):
            stats.ir_residual = "device"
            # cached per (trans, residual dtype) on the factorization —
            # the pdgsmv_init / SOLVEstruct discipline (SRC/pdgsmv.c:31).
            # The hit is guarded by data-array identity: FACTORED reuse
            # with a same-pattern matrix carrying NEW values must not
            # refine against the stale uploaded operator.  (In-place
            # mutation of a.data defeats any caching scheme — also true
            # of the reference's cached SOLVEstruct.)
            # identity-guard on the SOURCE a.data (op is derived from a
            # deterministically per trans, so transpose solves still hit)
            key = (trans, str(residual_dtype))
            cache = lu.dev_spmv if lu.dev_spmv is not None else {}
            hit = cache.get(key)
            ir_op = hit[1] if hit is not None and hit[0] is a.data else None
            if ir_op is None:
                from superlu_dist_tpu.parallel.dist import DeviceSpMV
                ir_op = DeviceSpMV(
                    op, dtype=np.result_type(op.data.dtype, residual_dtype))
                cache[key] = (a.data, ir_op)
                lu.dev_spmv = cache
        with stats.timer("REFINE"):
            x, berrs = iterative_refinement(ir_op, b, x, solve_fn,
                                            residual_dtype=residual_dtype)
        stats.refine_steps += len(berrs)
        lu.berrs = berrs
        report.berr_history = list(berrs)
        report.berr = berrs[-1] if berrs else None
        target = (recovery.berr_target if recovery.berr_target
                  else float(tols.berr_target(residual_dtype)))
        report.target = target
        bad = (report.berr is None or report.berr > target
               or not np.all(np.isfinite(np.asarray(x))))
        if recovery.enabled and bad:
            # the escalation ladder: each rung buys accuracy the previous
            # tier could not, and is recorded so the caller sees what
            # degraded and why the answer is still trustworthy
            x, lu_final, solve_fn, residual_dtype = _escalate(
                options, a, op, b, lu, stats, trans, solve_fn, x,
                residual_dtype, report, target)
        else:
            lu_final = lu
        report.refine_steps = len(report.berr_history)
        report.converged = (report.berr is not None
                            and report.berr <= target)
    else:
        lu_final = lu
        # NOREFINE + a reduced GEMM tier: the throughput ladder still
        # owes the caller a gated answer — one componentwise-BERR probe
        # (refine/ir.request_berrs, a single SpMV pair) stands in for
        # the refinement loop's measurement, and a miss runs the same
        # escalation ladder (which refines internally; opting out of IR
        # is not opting out of "never deliver a failing X")
        tier0 = getattr(lu.numeric, "gemm_prec", "highest")
        from superlu_dist_tpu.ops.dense import next_gemm_precision
        # armed only when the tier is a REAL gamble on this backend
        # (next_gemm_precision is None when the remaining rungs are
        # arithmetic no-ops — CPU's default tier IS the exact baseline,
        # and gating it would escalate answers the caller's NOREFINE +
        # factor_dtype choice deliberately left at factor precision)
        if recovery.enabled and next_gemm_precision(tier0) is not None:
            from superlu_dist_tpu.refine.ir import request_berrs
            target = (recovery.berr_target if recovery.berr_target
                      else float(tols.berr_target(np.float64)))
            report.target = target
            try:
                report.berr = float(request_berrs(op, b, x).max())
            except Exception:
                report.berr = None       # probe must never kill a solve
            bad = (report.berr is None or report.berr > target
                   or not np.all(np.isfinite(np.asarray(x))))
            if bad:
                x, lu_final, solve_fn, _ = _escalate(
                    options, a, op, b, lu, stats, trans, solve_fn, x,
                    np.float64, report, target)
            report.converged = (report.berr is not None
                                and report.berr <= target)

    # rcond/ferr (the pdgscon + dgsrfs-FERR reporting): "always", or on
    # "auto" only when the answer needs defending — the ladder fired,
    # tiny pivots were replaced, or refinement missed its target
    want_cond = (recovery.condest == "always"
                 or (recovery.condest == "auto"
                     and (report.rungs or report.tiny_pivots
                          or not report.converged)))
    if want_cond:
        from superlu_dist_tpu.refine.condest import (
            condition_estimate, ferr_estimate)
        report.rcond = condition_estimate(lu_final)
        cplx = np.issubdtype(np.asarray(a.data).dtype, np.complexfloating)
        adj_fn = _adjoint_solver(lu_final, trans, cplx)
        if adj_fn is not None and options.iter_refine != IterRefine.NOREFINE:
            try:
                report.ferr = ferr_estimate(op, b, x, solve_fn, adj_fn)
            except Exception:
                report.ferr = None       # estimation must never kill a solve

    # final non-finite sentinel: a silent NaN/Inf solution is the one
    # outcome the health subsystem exists to prevent
    report.finite = bool(np.all(np.isfinite(np.asarray(x))))
    if not report.finite and recovery.sentinels:
        raise NumericBreakdownError(where="solve")
    # end-to-end driver latency (SOLVE + refine + ladder + condest):
    # the "driver" series of the always-on latency accounter, so batch
    # users get the same quantile surface the serving fleet does
    lat = time.perf_counter() - t_req0
    report.latency_ms = round(lat * 1e3, 3)
    from superlu_dist_tpu.obs.slo import get_accounter
    get_accounter().observe(nrhs, lat, klass="driver")
    if options.print_stat:
        stats.print()
    return x, lu_final, stats, info

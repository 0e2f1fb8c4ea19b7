"""Multi-process expert driver over block-row distributed input.

Capability analog of pdgssvx with NR_loc input (SRC/pdgssvx.c:505): every
process holds a block of rows of A and of B (`DistributedCSR` — the
NRformat_loc analog), and all of them receive the solution.  Covers the
reference driver surface: multiple right-hand sides (nrhs ≥ 1, X returned
in B's shape), transpose solves (options.trans, pdgssvx.c's Trans
dispatch), and complex matrices (the pzgssvx twin — complex payloads ride
the f64 tree as re/im passes).

TPU-native split: the analysis + factorization are single-address-space
(they run where the accelerator is — rank 0), so the distributed input is
first assembled there, exactly like the reference's
pdCompRow_loc_to_CompCol_global gather before serial preprocessing
(pdgssvx.c:775).  The numeric work itself is SPMD-first: on a
single-controller mesh each factor group is one shard_map program and
each solve sweep one more (parallel/spmd.py — panels block-cyclic over
the flat device order, every extend-add/Schur/lsum exchange an
in-program collective; factor.get_executor's auto rule picks it), and
on a mesh spanning a jax.distributed world the GSPMD streamed kernels
shard over grid axes (parallel/grid.gridinit_multihost +
gssvx(grid=...)).  The host-mediated TreeComm lockstep tier is DEMOTED
to the A/B reference and recovery fallback: the root-gather path below
survives as the single-host fallback, its per-rank dispatch loop the
bitwise baseline the SPMD tier is gated against
(scripts/check_spmd_equiv.py, tests/test_spmd.py).  The
gather/broadcast ride the shared-memory tree collectives
(parallel/treecomm.py); refinement then runs distributed
(parallel/pgsrfs.py) so the residual work stays with the row owners —
the reference's pdgsrfs/pdgsmv shape.

Payloads larger than the tree domain's max_len stream through in chunks
(TreeComm.bcast_any/reduce_sum_any); integer index arrays travel on the
f64 mantissa (exact below 2^53 — dimensions and nnz counts are far
below).

Collective discipline: every rank must reach the same TreeComm
collective sequence.  slulint SLU101 verifies this statically
(interprocedurally since v2 — wrappers like bcast_result count as the
collectives they reach), and SLU_TPU_VERIFY_COLLECTIVES=1 verifies it
at runtime: each collective below then cross-checks a (call-site, op,
shape/dtype, seq) digest across ranks and raises
CollectiveMismatchError naming the divergent sites instead of
deadlocking (docs/ANALYSIS.md, rule SLU106).
"""

from __future__ import annotations

import numpy as np

from superlu_dist_tpu.parallel.dist import DistributedCSR
from superlu_dist_tpu.parallel.treecomm import TreeComm
from superlu_dist_tpu.sparse.formats import SparseCSR


def gather_distributed(tc: TreeComm, a_loc: DistributedCSR,
                       root: int = 0,
                       all_ranks: bool = False) -> SparseCSR | None:
    """Assemble the global CSR on `root` from every rank's block rows —
    the pdCompRow_loc_to_CompCol_global analog over tree collectives.
    Returns the matrix on root, None elsewhere.  all_ranks=True assembles
    on EVERY rank (all-reduce instead of reduce) — the analysis input for
    the mesh-sharded tier, where each controller must hold the same
    global pattern but no controller ever holds the factors."""
    n = a_loc.n
    # global nnz offsets: every rank's count, allreduced
    counts = np.zeros(tc.n_ranks)
    counts[tc.rank] = a_loc.nnz_loc
    counts = tc.allreduce_sum_any(counts, root=root)
    offs = np.zeros(tc.n_ranks + 1, dtype=np.int64)
    offs[1:] = np.cumsum(counts).astype(np.int64)
    total = int(offs[-1])
    lo = int(offs[tc.rank])
    _reduce = tc.allreduce_sum_any if all_ranks else tc.reduce_sum_any

    # row counts (for indptr) and flat index/value arrays, disjoint slots
    rowcnt = np.zeros(n)
    rowcnt[a_loc.fst_row:a_loc.fst_row + a_loc.m_loc] = \
        np.diff(a_loc.indptr)
    rowcnt = _reduce(rowcnt, root=root)
    idx = np.zeros(total)
    idx[lo:lo + a_loc.nnz_loc] = a_loc.indices
    idx = _reduce(idx, root=root)
    vdtype = (np.complex128 if np.issubdtype(np.asarray(a_loc.data).dtype,
                                             np.complexfloating)
              else np.float64)
    vals = np.zeros(total, dtype=vdtype)
    vals[lo:lo + a_loc.nnz_loc] = a_loc.data
    vals = _reduce(vals, root=root)

    if not all_ranks and tc.rank != root:
        return None
    indptr = np.zeros(n + 1, dtype=np.int64)
    indptr[1:] = np.cumsum(rowcnt).astype(np.int64)
    # ranks hold contiguous ascending row blocks, so the flat order by
    # rank offset IS row order
    return SparseCSR(n, n, indptr, idx.astype(np.int64), vals)


def _finish_stats(tc: TreeComm, lu_out):
    """Cross-rank stat epilogue — COLLECTIVE: every rank calls it at the
    same point, with NO dependence on per-rank ``lu_out`` presence (which
    may legitimately diverge across ranks).  Snapshots this rank's comm
    counters into its Stats, allreduces the fixed-layout stat vectors,
    and hands every rank the same StatsSummary: per-phase min/max/avg +
    load-balance factor — the sum-over-ranks PStatPrint the reference
    prints at PROFlevel≥1 (SRC/util.c:538-630).  ``SLU_TPU_STATS=1``
    prints the reduced report once, on rank 0."""
    from superlu_dist_tpu.utils.options import env_flag
    from superlu_dist_tpu.utils.stats import Stats

    stats = (lu_out or {}).get("stats")
    if stats is None:
        stats = Stats()
    stats.attach_comm(tc.comm_stats)
    summary = stats.reduce(tc)
    if lu_out is not None:
        lu_out["stats_summary"] = summary
    # serving metrics: cross-rank aggregation rides the same epilogue
    # (SLU_TPU_METRICS is env-driven, hence identical on every rank —
    # the branch is collective-safe)
    from superlu_dist_tpu.obs.metrics import get_metrics
    m = get_metrics()
    if m.enabled:
        reduced = m.reduce(tc)
        if lu_out is not None:
            lu_out["metrics_summary"] = reduced
    if env_flag("SLU_TPU_STATS") and tc.rank == 0:
        print(summary.report())
    return summary


def bcast_result(tc: TreeComm, fn, root: int = 0):
    """Run `fn()` on `root` and broadcast its result; a root-side
    exception is SHIPPED and re-raised on every rank instead of leaving
    the peers deadlocked in the broadcast (every root-serial section of
    the distributed tiers routes through this)."""
    payload = None
    if tc.rank == root:
        try:
            payload = (None, fn())
        except Exception as exc:
            payload = (exc, None)
    err, result = tc.bcast_obj(payload, root=root)
    if err is not None:
        raise err
    return result


def root_analyze_bcast(tc: TreeComm, options, a_loc: DistributedCSR,
                       stats, lu=None):
    """Gather the distributed rows on root, run the serial analysis
    there (honoring `lu` Fact-reuse), and broadcast the analyzed
    skeleton STRIPPED of the global matrix and the symmetrized-pattern
    copies (restored on root afterwards — they only serve future
    SamePattern reuse checks there).  Returns (lu, bvals) on every
    rank.  The one implementation behind _pgssvx_mesh's default tier,
    panalyze's small-problem fallback, and the A/B measurement script.
    """
    from superlu_dist_tpu.drivers.gssvx import analyze

    a_root = gather_distributed(tc, a_loc, root=0)
    sym_keep = None
    box = {}

    def _analyze():
        lu2, bvals, _ = analyze(options, a_root, lu=lu, stats=stats)
        lu2.a = None
        box["sym"] = (lu2.a_sym_indptr, lu2.a_sym_indices)
        lu2.a_sym_indptr = lu2.a_sym_indices = None
        return lu2, bvals

    lu2, bvals = bcast_result(tc, _analyze)
    if tc.rank == 0:
        lu2.a_sym_indptr, lu2.a_sym_indices = box["sym"]
    return lu2, bvals


def pgssvx(tc: TreeComm, options, a_loc: DistributedCSR,
           b_loc: np.ndarray, root: int = 0, grid=None, lu=None,
           lu_out=None, replicate_analysis: bool = False,
           resume_from: str | None = None):
    """Collectively solve op(A)·X = B from block-row distributed input.

    b_loc: (m_loc,) or (m_loc, nrhs) — this rank's block rows of B.
    Returns (x, info) on every rank, x of shape (n,) or (n, nrhs)
    matching b_loc.  options.trans selects op(A) (NOTRANS/TRANS/CONJ,
    the reference's pdgssvx trans dispatch); complex A/b take the
    pzgssvx path.

    `grid` (a parallel.grid.ProcessGrid whose mesh spans ALL the
    participating processes' devices, from gridinit_multihost) selects
    the distributed-factors tier: rank 0 assembles the global analysis
    input, runs the host analysis once, and broadcasts the analyzed
    skeleton; then all ranks run the SAME mesh-sharded factorization and
    collective device solve — the factors and the Schur pool live
    sharded across the processes' devices and NO process ever
    materializes them (the reference's defining NR_loc-in,
    distributed-factors-out property, SRC/pdgssvx.c:505 /
    pddistribute.c:322).  No non-root process assembles the global
    matrix or runs the analysis — it receives only the analysis products
    (plan/symbolic index maps + permuted values, O(nnz) data, measured
    ~2x lower peak host memory and wall time at n=110,592:
    docs/mesh_analysis_4proc_n110592.json; the psymbfact direction,
    SRC/psymbfact.c:228-242).  Without `grid`, the single-host fallback
    gathers to root and factors there (refinement stays distributed).

    `lu_out`: optional dict; on return, lu_out["lu"] holds this rank's
    LUFactorization handle (the reference's caller-owned LUstruct — on
    the fallback tier only the root has one) and lu_out["stats"] the
    factorization Stats (both tiers; on the fallback tier, root only).

    `lu`: a prior handle (this rank's lu_out["lu"] from an earlier
    call) activating options.fact's reuse tiers on the distributed
    input, the reference's time-stepping loop over NR_loc
    (EXAMPLE/pddrive1.c, pdgssvx.c Fact dispatch): SamePattern /
    SamePattern_SameRowPerm reuse the analysis products and refactor
    with the new values; FACTORED skips straight to the collective
    solve on the existing sharded factors.

    `resume_from` names a durable factor-checkpoint frontier
    (persist/checkpoint.py) for the ROOT factorization of the fallback
    tier — the rank-failure recovery path (parallel/recover.py,
    Options.ft="shrink"/"respawn") threads the previous epoch's
    checkpoint directory through here so the surviving ranks complete
    the factorization instead of redoing it; the fingerprint/digest
    verification inside gssvx guarantees the resumed frontier belongs
    to this exact analysis.  Rank failure itself surfaces here as
    RankFailureError on EVERY surviving rank (the bounded-wait
    collectives + failure detector in parallel/treecomm.py) — this
    driver never hangs on a dead peer once SLU_TPU_COMM_TIMEOUT_S is
    armed, and never retries on its own: recovery policy lives in
    parallel/recover.pgssvx_ft.

    Solve health: when refinement ran, lu_out["stats"].solve_report
    carries berr (+ history) from the distributed loop; if it stagnated
    above the recovery target and options.recovery is enabled, ONE
    escalated retry at the next factor-precision tier runs collectively
    (the decision is taken from allreduced quantities, so every rank
    agrees — no rank-divergent control flow) and is recorded as a rung.
    """
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.parallel.pgsrfs import pgsrfs
    from superlu_dist_tpu.utils.errors import CheckpointError
    from superlu_dist_tpu.utils.options import IterRefine, Trans
    import dataclasses

    n = a_loc.n
    b_loc = np.asarray(b_loc)
    one_d = b_loc.ndim == 1
    # NOT reshape(m_loc, -1): on an empty trailing block (m_loc == 0,
    # legitimate from distribute_rows' ceil stepping) reshape(0, -1)
    # raises and the surviving ranks would deadlock in the collectives
    b2 = b_loc[:, None] if one_d else b_loc
    nrhs = b2.shape[1]
    complex_in = (np.issubdtype(np.asarray(a_loc.data).dtype,
                                np.complexfloating)
                  or np.issubdtype(b2.dtype, np.complexfloating))
    wdtype = np.complex128 if complex_in else np.float64

    if grid is not None:
        x, info, rep = _pgssvx_mesh(tc, options, a_loc, b2, grid, one_d,
                                    wdtype, lu=lu, lu_out=lu_out,
                                    replicate_analysis=replicate_analysis)
        x, info = _maybe_escalate_distributed(
            tc, options, a_loc, b_loc, x, info, rep, lu_out, grid=grid,
            replicate_analysis=replicate_analysis)
        # cross-rank stat reduction (collective; the escalate decision
        # above is replicated, so every rank reaches this together)
        _finish_stats(tc, lu_out)
        return x, info

    a_root = gather_distributed(tc, a_loc, root=root)
    b_full = np.zeros((n, nrhs), dtype=wdtype)
    b_full[a_loc.fst_row:a_loc.fst_row + a_loc.m_loc] = b2
    b_full = tc.reduce_sum_any(b_full, root=root)

    x0 = np.zeros((n, nrhs), dtype=wdtype)
    info = np.zeros(1)
    solve_fn = None
    if tc.rank == root:
        # refinement happens distributed below — root factors only;
        # `lu` threads the Fact reuse tiers through (root-held handle)
        opts0 = dataclasses.replace(options,
                                    iter_refine=IterRefine.NOREFINE)
        try:
            x_r, lu, stats, info_r = gssvx(
                opts0, a_root, b_full if nrhs > 1 else b_full[:, 0],
                lu=lu, resume_from=resume_from)
        except CheckpointError:
            # an unusable recovery frontier (corrupt / wrong plan) must
            # degrade to a from-scratch factorization, not strand the
            # peers: the retry is root-LOCAL and leaves the collective
            # sequence untouched (the peers only see the info bcast)
            x_r, lu, stats, info_r = gssvx(
                opts0, a_root, b_full if nrhs > 1 else b_full[:, 0],
                lu=lu)
        info[0] = float(info_r)
        if lu_out is not None:
            lu_out["lu"] = lu
            lu_out["stats"] = stats
        if info_r == 0:
            x0 = np.asarray(x_r, dtype=wdtype).reshape(n, nrhs)
            trans = getattr(options, "trans", Trans.NOTRANS)
            if trans == Trans.NOTRANS:
                solve_fn = lu.solve_factored
            else:
                conj = trans == Trans.CONJ
                solve_fn = (lambda r:
                            lu.solve_factored_trans(r, conj=conj))
    info = tc.bcast_any(info, root=root)
    if int(info[0]) != 0:
        return None, int(info[0])
    x0 = tc.bcast_any(x0, root=root)
    x, info_out, rep = _refine_tail(tc, options, a_loc, b2, x0, solve_fn,
                                    root, one_d, nrhs, lu_out=lu_out)
    x, info_out = _maybe_escalate_distributed(tc, options, a_loc, b_loc, x,
                                              info_out, rep, lu_out,
                                              root=root)
    _finish_stats(tc, lu_out)
    return x, info_out


def _refine_tail(tc, options, a_loc, b2, x0, solve_fn, root, one_d, nrhs,
                 lu_out=None, collective_solve=False, stats=None):
    """Distributed refinement over the RHS columns; returns
    (x, info, SolveReport-or-None).  The report is identical on every
    rank (built from allreduced berr values), so callers may branch on
    it collectively."""
    from superlu_dist_tpu.parallel.pgsrfs import pgsrfs
    from superlu_dist_tpu.utils.options import IterRefine, Trans
    rep = None
    if options.iter_refine == IterRefine.NOREFINE:
        x = x0
    else:
        # per-RHS distributed refinement (the reference's pdgsrfs loops
        # RHS columns with per-RHS berr, pdgsrfs.c:205-235)
        trans = getattr(options, "trans", Trans.NOTRANS)
        cols = []
        rhs_stats = []
        for j in range(nrhs):
            so = {}
            cols.append(pgsrfs(tc, a_loc, b2[:, j], x0[:, j], solve_fn,
                               root=root, trans=trans,
                               collective_solve=collective_solve,
                               stats_out=so))
            rhs_stats.append(so)
        x = np.stack(cols, axis=1)
        rep = _attach_distributed_report(options, rhs_stats, x,
                                         lu_out=lu_out, stats=stats)
    return (x[:, 0] if one_d else x), 0, rep


def _attach_distributed_report(options, rhs_stats, x, lu_out=None,
                               stats=None):
    """Build the SolveReport of a distributed refinement (every rank sees
    the same allreduced berr values, so every rank builds the same
    report) and attach it to the Stats handed back via lu_out."""
    from superlu_dist_tpu.utils.stats import SolveReport
    berrs = [s["berr"] for s in rhs_stats if s.get("berr") is not None]
    target = (options.recovery.berr_target
              or 10.0 * float(np.finfo(np.float64).eps))
    rep = SolveReport(
        berr=max(berrs) if berrs else None,
        berr_history=[b for s in rhs_stats for b in s.get("berrs", [])],
        target=target,
        finite=bool(np.all(np.isfinite(x))))
    rep.refine_steps = sum(s.get("iters", 0) for s in rhs_stats)
    rep.converged = rep.berr is not None and rep.berr <= target
    if stats is None and lu_out is not None:
        stats = lu_out.get("stats")
    if stats is not None:
        # the root factorization's NOREFINE report carries the
        # factorization facts; the distributed refinement supersedes it
        # but inherits them
        prev = stats.solve_report
        if prev is not None:
            rep.tiny_pivots = prev.tiny_pivots
            rep.factor_dtype = prev.factor_dtype
            rep.rcond = prev.rcond
        stats.solve_report = rep
    if lu_out is not None:
        lu_out["solve_report"] = rep
    return rep


def _maybe_escalate_distributed(tc, options, a_loc, b_loc, x, info, rep,
                                lu_out, root=0, grid=None,
                                replicate_analysis=False):
    """One collective escalation rung for the distributed driver: when
    the distributed refinement stagnated above the recovery target,
    rerun the whole flow at the next factor-precision tier.  Every input
    to the decision (the report's berr/target, the shared options) is
    replicated, so all ranks take the same branch — rank-divergent
    control flow here would strand peers in the collectives (which is
    also why the decision must NOT depend on per-rank lu_out presence)."""
    import dataclasses

    from superlu_dist_tpu.drivers.gssvx import _escalation_dtype
    from superlu_dist_tpu.utils.options import Fact, IterRefine
    from superlu_dist_tpu.utils.stats import RungRecord

    recovery = options.recovery
    if (info != 0 or rep is None or rep.converged
            or not recovery.enabled
            or options.iter_refine == IterRefine.NOREFINE):
        return x, info
    from superlu_dist_tpu.utils.options import default_factor_dtype
    cur = options.factor_dtype or default_factor_dtype()
    esc = _escalation_dtype(cur)
    if esc is None:
        return x, info
    opts2 = dataclasses.replace(
        options, fact=Fact.DOFACT, factor_dtype=esc,
        recovery=dataclasses.replace(recovery, enabled=False))
    lu_out2 = {}
    x2, info2 = pgssvx(tc, opts2, a_loc, b_loc, root=root, grid=grid,
                       lu_out=lu_out2,
                       replicate_analysis=replicate_analysis)
    rep2 = lu_out2.get("solve_report")
    berr2 = rep2.berr if rep2 is not None and rep2.berr is not None \
        else float("inf")
    rung = RungRecord(name="distributed-hiprec", detail=str(esc),
                      berr_before=rep.berr, berr_after=berr2)
    rep.rungs.append(rung)
    if info2 == 0 and berr2 < rep.berr:
        rep.berr = berr2
        rep.berr_history.extend(rep2.berr_history if rep2 else [])
        rep.converged = berr2 <= rep.target
        rep.finite = bool(np.all(np.isfinite(x2)))
        if lu_out is not None:
            # the answer now rests on the escalated factors/handle
            lu_out.update(lu_out2)
            lu_out["solve_report"] = rep
        return x2, info2
    return x, info


def _pgssvx_mesh(tc, options, a_loc, b2, grid, one_d, wdtype,
                 lu=None, lu_out=None, replicate_analysis=False):
    """Distributed-factors tier: rank 0 assembles the global analysis
    input and runs the host analysis ONCE, then broadcasts the analyzed
    skeleton (symbolic + plan + transforms + permuted values) over the
    tree — O(nnz) transfer instead of O(nnz) redundant analysis work and
    graph memory on every rank, the wall the reference's distributed
    symbolic was built to break (SRC/psymbfact.c:140,228-242,
    get_perm_c_parmetis.c:104).  All ranks then run ONE mesh-sharded
    numeric factorization in lockstep — the factors, Schur pool, and
    triangular solves are SPMD programs over the grid's (multi-process)
    mesh, so the factors stay sharded across the processes' devices for
    their whole lifetime.  The collective correction solve also serves
    the distributed refinement loop (every rank calls it — the pdgsrfs
    shape where pdgstrs is itself parallel, SRC/pdgsrfs.c:205).

    replicate_analysis=True restores the round-4 every-rank-analyzes
    behavior (kept for A/B measurement, scripts/mesh_analysis_scale.py).
    """
    import dataclasses

    from superlu_dist_tpu.drivers.gssvx import analyze, factorize_numeric
    from superlu_dist_tpu.parallel.pgsrfs import pgsrfs
    from superlu_dist_tpu.utils.errors import SuperLUError
    from superlu_dist_tpu.utils.options import Fact, IterRefine, Trans
    from superlu_dist_tpu.utils.stats import Stats

    n = a_loc.n
    nrhs = b2.shape[1]
    b_full = np.zeros((n, nrhs), dtype=wdtype)
    b_full[a_loc.fst_row:a_loc.fst_row + a_loc.m_loc] = b2
    b_full = tc.allreduce_sum_any(b_full, root=0)

    # refinement runs distributed below (block rows stay with their
    # owners), so the skeleton travels WITHOUT the global matrix: a
    # non-root rank never materializes A, only the analysis products
    opts0 = dataclasses.replace(options, iter_refine=IterRefine.NOREFINE)
    stats = Stats()
    fact = getattr(options, "fact", Fact.DOFACT)
    if fact == Fact.FACTORED:
        # solve-only on the existing sharded factors (every rank holds
        # ITS handle from a prior call's lu_out — pdgssvx's Fact=
        # FACTORED over the grid); the solves below are collective, so
        # a missing handle must fail on EVERY rank, not strand the
        # others inside the SPMD solve
        ok = np.zeros(1)
        ok[0] = 1.0 if (lu is not None and lu.numeric is not None) \
            else 0.0
        ok = tc.allreduce_sum_any(ok)
        if int(ok[0]) != tc.n_ranks:
            raise SuperLUError(
                "Fact=FACTORED requires EVERY rank's prior lu handle "
                f"({int(ok[0])}/{tc.n_ranks} ranks have one)")
        info_r = 0
    elif replicate_analysis:
        a_all = gather_distributed(tc, a_loc, all_ranks=True)
        lu, bvals, _ = analyze(opts0, a_all, stats=stats)
        lu.a = None
    elif getattr(opts0, "par_symb_fact", False):
        # ParSymbFact tier: ordering + symbolic partition across the
        # ranks themselves (parallel/panalysis.py — the ParMETIS +
        # psymbfact shape); root only assembles and plans
        from superlu_dist_tpu.parallel.panalysis import panalyze
        lu, bvals = panalyze(tc, opts0, a_loc, stats=stats)
    else:
        # `lu` (root's prior handle) activates the SamePattern reuse
        # tiers inside analyze
        lu, bvals = root_analyze_bcast(tc, opts0, a_loc, stats, lu=lu)
    if fact != Fact.FACTORED:
        # deadline_comm=tc: Options.deadline_s expiry becomes a
        # COLLECTIVE decision (flag allreduce per poll inside the factor
        # loop, utils/deadline.py), so DeadlineExceededError raises on
        # every rank together — cancellation can never strand a peer in
        # a collective (the SLU101/SLU106 discipline)
        info_r = factorize_numeric(lu, bvals, stats, grid=grid,
                                   deadline_comm=tc)
    if lu_out is not None:
        lu_out["lu"] = lu
        lu_out["stats"] = stats
    if info_r != 0:
        return None, int(info_r), None
    trans = getattr(options, "trans", Trans.NOTRANS)
    if trans == Trans.NOTRANS:
        solve_fn = lu.solve_factored
    else:
        solve_fn = (lambda r: lu.solve_factored_trans(
            r, conj=trans == Trans.CONJ))
    with stats.timer("SOLVE"):
        x_r = solve_fn(b_full if nrhs > 1 else b_full[:, 0])
    x0 = np.asarray(x_r, dtype=wdtype).reshape(n, nrhs)
    # collective_solve=True: every rank calls solve_fn (the mesh solve is
    # an SPMD program all controllers must enter), so no dx broadcast
    return _refine_tail(tc, options, a_loc, b2, x0, solve_fn, 0, one_d,
                        nrhs, lu_out=lu_out, collective_solve=True,
                        stats=stats)

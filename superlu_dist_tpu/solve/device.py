"""Device-resident supernodal triangular solves.

Analog of pdgstrs (SRC/pdgstrs.c:838) + the lsum kernels
(SRC/pdgstrs_lsum.c:413,1360): forward solve L·y = d walking the supernode
tree bottom-up, backward solve U·x = y walking it back down.  Where the
reference runs an MPI event loop over per-supernode broadcast/reduce trees
with OpenMP-task lsum updates, here each sweep batch is one batched
kernel: gather RHS segments, a (recursively blocked) triangular solve on
the MXU, and a scatter-add of the L21·y (resp. U12·x) contributions — the
lsum vector lives in device HBM, playing the role of the reference's
distributed lsum buffers.

Sweep batches come from a :class:`~superlu_dist_tpu.solve.plan.SolvePlan`
(solve/plan.py): the PR 5 dataflow machinery regroups supernodes across
elimination levels into maximal same-shape batches, with a second
shape-key alignment pass on top of the factor keys.  Batches that
coincide with a factor group alias its front arrays (zero copy); merged
batches gather — and, for promoted keys, identity/zero-pad — a fresh
panel stack once at solver construction.

Many-RHS support is first-class: request widths map onto a CLOSED nrhs
bucket set (power-of-two rungs then bounded geometric growth,
solve/plan.py) and anything past the cap is column-chunked, so one
serving process compiles at most |buckets| kernel variants per sweep
shape no matter what traffic arrives.  Large supernode diagonal blocks
solve via recursive blocked TRSM (``SLU_TPU_SOLVE_TRSM_LEAF``): the
recursion turns all but the leaf triangles into batched GEMMs the MXU
can run at rate (arXiv:2504.13821's recursive TRSM, batched).

Factors never leave the device (the reference's analog: factors stay in
each rank's memory between pdgstrf and pdgstrs); only the right-hand side
(n·nrhs) crosses the host boundary.  Like the factorization executors, one
kernel compiles per distinct (batch, m, w, u, nrhs-bucket) shape and is
cached persistently.
"""

from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from superlu_dist_tpu.numeric.factor import NumericFactorization
from superlu_dist_tpu.obs.compilestats import call, tally
from superlu_dist_tpu.obs.trace import get_tracer
from superlu_dist_tpu.solve.plan import SolvePlan, build_solve_plan, chunk_nrhs


def _audit_sweep(label: str, kern, args, dead) -> None:
    """Submit one sweep program to the runtime IR auditor
    (SLU_TPU_VERIFY_PROGRAMS=1; allocates nothing when off).  ``dead``
    names the RHS/lsum argnums each sweep consumes — they are donated
    by every kernel factory above, which is what SLU111 verifies."""
    from superlu_dist_tpu.utils.programaudit import maybe_audit
    maybe_audit("solve.device", label, kern, args, dead=dead)


def _trsm(a, b, lower, unit, trans, leaf, prec="highest"):
    """Batched triangular solve op(a)·x = b with recursive blocking.

    a is (B, w, w), b is (B, w, k).  At or below ``leaf`` the vmapped
    LAPACK-style solve runs directly; above it the triangle splits in
    half and the off-diagonal block becomes one batched GEMM — the
    recursive blocked TRSM that keeps large diagonal blocks on the MXU
    instead of in a length-w dependent chain (leaf <= 0 disables
    blocking entirely).  ``prec`` is the caller-resolved GEMM-precision
    ladder tier (ops/dense.gemm_precision) the off-diagonal GEMMs run at
    — the solve-side half of the throughput ladder; the leaf triangles
    themselves always solve at full precision.  Conjugation is the
    caller's job (conj the triangle before calling, as the trans sweeps
    already do)."""
    from superlu_dist_tpu.ops.dense import gemm
    w = a.shape[-1]
    if leaf <= 0 or w <= leaf:
        return jax.vmap(lambda m, r: jax.scipy.linalg.solve_triangular(
            m, r, lower=lower, unit_diagonal=unit, trans=trans))(a, b)
    h = w // 2
    a11, a22 = a[:, :h, :h], a[:, h:, h:]
    b1, b2 = b[:, :h], b[:, h:]
    if lower != bool(trans):
        # dependency runs top-down: x1 first, then fold A21·x1 (notrans
        # lower) / A12ᵀ·x1 (trans upper) out of b2
        off = a[:, h:, :h] if lower else jnp.swapaxes(a[:, :h, h:], 1, 2)
        x1 = _trsm(a11, b1, lower, unit, trans, leaf, prec)
        x2 = _trsm(a22, b2 - gemm(off, x1, prec),
                   lower, unit, trans, leaf, prec)
    else:
        # bottom-up: x2 first (notrans upper / trans lower)
        off = a[:, :h, h:] if not lower else jnp.swapaxes(a[:, h:, :h], 1, 2)
        x2 = _trsm(a22, b2, lower, unit, trans, leaf, prec)
        x1 = _trsm(a11, b1 - gemm(off, x2, prec),
                   lower, unit, trans, leaf, prec)
    return jnp.concatenate([x1, x2], axis=1)


def _fwd_body(lpanel, x, lsum, first, rows, ws, w, u, n, use_inv, linv,
              leaf, prec="highest"):
    """x[cols] <- L11⁻¹(x[cols] − lsum[cols]); lsum[rows] += L21·x[cols].

    With use_inv, L11⁻¹ arrives precomputed and the triangular solve
    becomes one batched GEMM (the reference's DiagInv fast path,
    pdgstrs.c:1252-1396: dense X(k) = Linv(k)·b via dgemm)."""
    k = jnp.arange(w)
    # padded pivot columns (k >= ws) would alias the NEXT supernode's
    # entries — clamp them to the dump row n-1 (factor cols/rows there
    # are exactly identity/zero, so the garbage never reaches real x)
    cols = jnp.where(k[None, :] < ws[:, None],
                     first[:, None] + k, n - 1)      # (B, w)
    rhs = (x.at[cols].get(mode="fill", fill_value=0)
           - lsum.at[cols].get(mode="fill", fill_value=0))
    if use_inv:
        # same-dtype preferred_element_type pins are no-ops bitwise —
        # they make the accumulation width explicit (slulint SLU116)
        y = jnp.matmul(linv, rhs, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=rhs.dtype)
    else:
        y = _trsm(lpanel[:, :w, :w], rhs, lower=True, unit=True,
                  trans=0, leaf=leaf, prec=prec)
    x = x.at[cols].set(y, mode="drop")
    if u:
        contrib = jnp.matmul(lpanel[:, w:, :], y,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=y.dtype)
        lsum = lsum.at[rows].add(contrib, mode="drop")
    return x, lsum


def _bwd_body(lpanel, upanel, x, first, rows, ws, w, u, n, use_inv, uinv,
              leaf, prec="highest"):
    """x[cols] <- U11⁻¹(x[cols] − U12·x[rows])."""
    k = jnp.arange(w)
    cols = jnp.where(k[None, :] < ws[:, None],
                     first[:, None] + k, n - 1)
    rhs = x.at[cols].get(mode="fill", fill_value=0)
    if u:
        xr = x.at[rows].get(mode="fill", fill_value=0)   # (B, u, nrhs)
        rhs = rhs - jnp.matmul(upanel, xr,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=xr.dtype)
    if use_inv:
        y = jnp.matmul(uinv, rhs, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=rhs.dtype)
    else:
        y = _trsm(lpanel[:, :w, :w], rhs, lower=False, unit=False,
                  trans=0, leaf=leaf, prec=prec)
    return x.at[cols].set(y, mode="drop")


def _fwd_body_trans(lpanel, upanel, x, lsum, first, rows, ws, w, u, n,
                    conj, leaf, prec="highest"):
    """Transpose forward sweep: x[cols] <- U11⁻ᵀ(x[cols] − lsum[cols]);
    lsum[rows] += U12ᵀ·x[cols].  Mᵀ = UᵀLᵀ, so Uᵀ (lower) leads — the
    trans_t path through the same factors (superlu_defs.h:628-657)."""
    k = jnp.arange(w)
    cols = jnp.where(k[None, :] < ws[:, None],
                     first[:, None] + k, n - 1)
    rhs = (x.at[cols].get(mode="fill", fill_value=0)
           - lsum.at[cols].get(mode="fill", fill_value=0))
    u11 = lpanel[:, :w, :w]
    if conj:
        u11 = u11.conj()
    y = _trsm(u11, rhs, lower=False, unit=False, trans=1, leaf=leaf,
              prec=prec)
    x = x.at[cols].set(y, mode="drop")
    if u:
        u12 = upanel.conj() if conj else upanel       # (B, w, u)
        contrib = jnp.matmul(jnp.swapaxes(u12, 1, 2), y,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=y.dtype)
        lsum = lsum.at[rows].add(contrib, mode="drop")
    return x, lsum


def _bwd_body_trans(lpanel, x, first, rows, ws, w, u, n, conj, leaf,
                    prec="highest"):
    """Transpose backward sweep: x[cols] <- L11⁻ᵀ(x[cols] − L21ᵀ·x[rows])."""
    k = jnp.arange(w)
    cols = jnp.where(k[None, :] < ws[:, None],
                     first[:, None] + k, n - 1)
    rhs = x.at[cols].get(mode="fill", fill_value=0)
    if u:
        xr = x.at[rows].get(mode="fill", fill_value=0)
        l21 = lpanel[:, w:, :]                         # (B, u_pad, w)
        if conj:
            l21 = l21.conj()
        rhs = rhs - jnp.matmul(jnp.swapaxes(l21, 1, 2), xr,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=xr.dtype)
    l11 = lpanel[:, :w, :w]
    if conj:
        l11 = l11.conj()
    y = _trsm(l11, rhs, lower=True, unit=True, trans=1, leaf=leaf,
              prec=prec)
    return x.at[cols].set(y, mode="drop")


# Program names (the ``jit_<name>`` module a device trace reports): the
# forward and backward sweeps are ``solve_fwd`` / ``solve_bwd`` whether
# fused or per batch, the transpose pair ``solve_fwd_trans`` /
# ``solve_bwd_trans``.

@functools.lru_cache(maxsize=None)
def _fwd_kernel(batch, m, w, u, nrhs, n, dtype, use_inv=False, leaf=0,
                prec="highest"):
    def solve_fwd(lpanel, x, lsum, first, rows, ws, linv=None):
        return _fwd_body(lpanel, x, lsum, first, rows, ws, w, u, n,
                         use_inv, linv, leaf, prec)

    return jax.jit(solve_fwd, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def _bwd_kernel(batch, m, w, u, nrhs, n, dtype, use_inv=False, leaf=0,
                prec="highest"):
    def solve_bwd(lpanel, upanel, x, first, rows, ws, uinv=None):
        return _bwd_body(lpanel, upanel, x, first, rows, ws, w, u, n,
                         use_inv, uinv, leaf, prec)

    return jax.jit(solve_bwd, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _fwd_trans_kernel(batch, m, w, u, nrhs, n, dtype, conj=False, leaf=0,
                      prec="highest"):
    def solve_fwd_trans(lpanel, upanel, x, lsum, first, rows, ws):
        return _fwd_body_trans(lpanel, upanel, x, lsum, first, rows, ws,
                               w, u, n, conj, leaf, prec)

    return jax.jit(solve_fwd_trans, donate_argnums=(2, 3))


@functools.lru_cache(maxsize=None)
def _bwd_trans_kernel(batch, m, w, u, nrhs, n, dtype, conj=False, leaf=0,
                      prec="highest"):
    def solve_bwd_trans(lpanel, x, first, rows, ws):
        return _bwd_body_trans(lpanel, x, first, rows, ws, w, u, n, conj,
                               leaf, prec)

    return jax.jit(solve_bwd_trans, donate_argnums=(1,))


@functools.lru_cache(maxsize=None)
def _diag_inv_kernel(w, dtype, leaf=0, prec="highest"):
    """Batched inverses of the packed diagonal blocks — the
    pdCompute_Diag_Inv analog (SRC/pdgstrs.c:647, dtrtri per block)."""

    def solve_diag_inv(lpanel):
        f11 = lpanel[:, :w, :w]
        eye = jnp.broadcast_to(jnp.eye(w, dtype=lpanel.dtype),
                               f11.shape)
        linv = _trsm(f11, eye, lower=True, unit=True, trans=0, leaf=leaf,
                     prec=prec)
        uinv = _trsm(f11, eye, lower=False, unit=False, trans=0,
                     leaf=leaf, prec=prec)
        return linv, uinv

    return jax.jit(solve_diag_inv)


# The fused sweeps: one program per sweep over every batch of a plan.
# The key is everything the traced body reads that is not an argument —
# the per-batch (w, u) in sweep order, n + 1 and the solver's settings —
# so every solver of one plan (each refactor's, a ladder rung's, the
# condition estimate's) shares one jitted program; panels, index maps
# and the RHS are arguments, keyed by jit on shape and dtype.  Bounded:
# each entry holds its compiled executables.
_FUSED_PLANS = 64


@functools.lru_cache(maxsize=_FUSED_PLANS)
def _fused_kernels(meta, n, use_inv=False, leaf=0, prec="highest"):
    def solve_fwd(x, lsum, fronts, idx, invs):
        for (w, u), (lp, _), (firsts, rows, ws), (linv, _) in zip(
                meta, fronts, idx, invs):
            x, lsum = _fwd_body(lp, x, lsum, firsts, rows, ws, w, u, n,
                                use_inv, linv, leaf, prec)
        return x, lsum

    def solve_bwd(x, fronts, idx, invs):
        for (w, u), (lp, up), (firsts, rows, ws), (_, uinv) in zip(
                reversed(meta), reversed(fronts), reversed(idx),
                reversed(invs)):
            x = _bwd_body(lp, up, x, firsts, rows, ws, w, u, n, use_inv,
                          uinv, leaf, prec)
        return x

    return (jax.jit(solve_fwd, donate_argnums=(0, 1)),
            jax.jit(solve_bwd, donate_argnums=(0,)))


@functools.lru_cache(maxsize=_FUSED_PLANS)
def _fused_trans_kernels(meta, n, conj=False, leaf=0, prec="highest"):
    def solve_fwd_trans(x, lsum, fronts, idx):
        for (w, u), (lp, up), (firsts, rows, ws) in zip(meta, fronts, idx):
            x, lsum = _fwd_body_trans(lp, up, x, lsum, firsts, rows, ws,
                                      w, u, n, conj, leaf, prec)
        return x, lsum

    def solve_bwd_trans(x, fronts, idx):
        for (w, u), (lp, _), (firsts, rows, ws) in zip(
                reversed(meta), reversed(fronts), reversed(idx)):
            x = _bwd_body_trans(lp, x, firsts, rows, ws, w, u, n, conj,
                                leaf, prec)
        return x

    return (jax.jit(solve_fwd_trans, donate_argnums=(0, 1)),
            jax.jit(solve_bwd_trans, donate_argnums=(0,)))


def _pad_panels(lp, up, w0, u0, W, U):
    """Promote one factor group's panel stack from its (w0, u0) padding
    to a merged solve key (W, U): identity on the new pivot diagonal
    (benign under both the unit-lower and the non-unit upper solves —
    padded columns gather from and write to the dump row only), zeros
    everywhere else so padded L21/U12 contributions vanish exactly."""
    piv, l21 = lp[:, :w0, :w0], lp[:, w0:, :]
    dw, du = W - w0, U - u0
    piv = jnp.pad(piv, ((0, 0), (0, dw), (0, dw)))
    if dw:
        idx = jnp.arange(w0, W)
        piv = piv.at[:, idx, idx].set(1)
    l21 = jnp.pad(l21, ((0, 0), (0, du), (0, dw)))
    return (jnp.concatenate([piv, l21], axis=1),
            jnp.pad(up, ((0, 0), (0, dw), (0, du))))


class DeviceSolver:
    """Solve (L·U)x = d on the device, in the factor's permuted labeling.

    The dSOLVEstruct_t analog (superlu_ddefs.h:216-228): the sweep
    schedule (a SolvePlan), per-batch index maps and panel stacks are
    built once and reused across repeated solves (the reference caches
    them behind SolveInitialized, pdgssvx.c:1330-1337).

    fused=True traces each whole sweep (all batches) into ONE jitted XLA
    program per nrhs bucket — one dispatch for the forward solve and one
    for the backward instead of one per sweep batch.  The solve is
    latency-bound (tiny per-level GEMVs — SURVEY.md §7 hard-part 5:
    "tree-based trisolve is tiny-message dominated"), so collapsing the
    dispatch chain is the device analog of the reference's fully
    pipelined event loop.  Compile cost grows with the plan, so "auto"
    fuses only moderate plans.
    """

    def __init__(self, fact: NumericFactorization, diag_inv: bool = False,
                 fused: str | bool = "auto", mesh=None,
                 solve_plan: SolvePlan | None = None,
                 schedule: str | None = None, window: int | None = None,
                 align: float | None = None, trsm_leaf: int | None = None,
                 nrhs_max: int | None = None,
                 nrhs_growth: float | None = None,
                 gemm_prec: str | None = None):
        """mesh: a jax.sharding.Mesh the factors are sharded over.  Needed
        when the mesh spans MULTIPLE PROCESSES (the pdgstrs-over-the-grid
        case): the RHS then uploads replicated over the global mesh and
        the index maps stay numpy (pjit treats identical host arrays as
        replicated global inputs), so every controller runs the same SPMD
        sweeps and reads the replicated result locally.  On such a
        MULTI-PROCESS mesh the sweep schedule is pinned to "factor" —
        re-gathering panel stacks into dataflow sweep batches would
        commit non-addressable shards to one local device (solve/plan.py
        documents the rationale) — so those solves keep the factor
        grouping 1:1.  Single-process mesh solves are NOT pinned: one
        controller addresses every device, so the dataflow solve
        schedule applies, and the shard_map tier (parallel/spmd.SpmdSolver,
        which subclasses this with mesh=None) always uses it."""
        self.fact = fact
        self.diag_inv = diag_inv
        self.mesh = mesh
        plan = fact.plan
        if trsm_leaf is None:
            from superlu_dist_tpu.utils.options import env_int
            trsm_leaf = env_int("SLU_TPU_SOLVE_TRSM_LEAF")
        self.trsm_leaf = int(trsm_leaf)
        # GEMM-precision ladder tier for the blocked-TRSM off-diagonal
        # GEMMs (ops/dense.gemm_precision — the solve-side half of the
        # throughput ladder), resolved in this uncached constructor and
        # part of every sweep-kernel cache key below
        from superlu_dist_tpu.ops.dense import gemm_precision
        self.gemm_prec = gemm_precision(gemm_prec)
        if mesh is not None and jax.process_count() > 1:
            # the factor-schedule pin is a MULTI-PROCESS constraint only
            # (docstring above; solve/plan.py) — single-process meshes
            # keep the dataflow solve schedule like any local solve
            solve_plan = build_solve_plan(plan, schedule="factor",
                                          nrhs_max=nrhs_max,
                                          nrhs_growth=nrhs_growth)
        elif solve_plan is None:
            solve_plan = build_solve_plan(plan, schedule=schedule,
                                          window=window, align=align,
                                          nrhs_max=nrhs_max,
                                          nrhs_growth=nrhs_growth)
        self.splan = solve_plan
        self.last_solve_stats = None
        if fused == "auto":
            fused = len(solve_plan.groups) <= 256
        self.fused = bool(fused)
        self._replicate = None
        sf = plan.sf
        self.n = plan.n
        first = sf.sn_start[:-1]
        self._groups = []
        self._invs_cached = None
        # with a (multi-process) mesh the index arrays must not commit to
        # one local device — numpy args are what pjit accepts uniformly
        _put = (lambda x: np.asarray(x)) if mesh is not None else jnp.asarray
        # a host-share factorization (stream.py SLU_TPU_HOST_FLOPS) leaves
        # the leading leaf panels as numpy: upload those once so the
        # jitted sweeps don't re-transfer them on every solve.  The
        # uploaded list lives on the SOLVER — assigning back to
        # fact.fronts would silently flip fact.on_host and force a
        # later host solve on the same factorization to re-pull everything
        if (any(isinstance(lp, np.ndarray) for lp, _ in fact.fronts)
                and not fact.on_host):
            # stream.py disables host-share under a mesh; enforce that
            # invariant HERE too — jnp.asarray would commit these fronts
            # to one local device and break a multi-process SPMD solve
            assert mesh is None, \
                "host-share fronts cannot meet a multi-process mesh solve"
            src_fronts = [(jnp.asarray(lp), jnp.asarray(up))
                          for lp, up in fact.fronts]
        else:
            src_fronts = fact.fronts
        panels = []
        for sg in solve_plan.groups:
            if sg.reuse >= 0:
                panels.append(src_fronts[sg.reuse])
            else:
                panels.append(self._gather_panels(sg, src_fronts, plan))
            firsts = _put(first[sg.sns])
            rows = np.full((sg.batch, sg.u), self.n, dtype=np.int64)
            for slot, s in enumerate(sg.sns):
                r = sf.sn_rows[s]
                rows[slot, :len(r)] = r
            self._groups.append((sg, firsts, _put(rows), _put(sg.ws)))
        self.fronts = panels
        # the fused sweeps' static key: each batch's (w, u), sweep order
        self._meta = tuple((grp.w, grp.u) for grp, _, _, _ in self._groups)

    @staticmethod
    def _gather_panels(sg, src_fronts, plan):
        """Assemble one merged sweep batch's panel stack from the factor
        fronts: per contiguous source-group run one fancy-index gather,
        promoted keys identity/zero-padded, all concatenated in member
        (slot) order.  Runs once at construction, on device."""
        parts_l, parts_u = [], []
        i, B = 0, sg.batch
        while i < B:
            g = int(sg.src_group[i])
            j = i
            while j < B and int(sg.src_group[j]) == g:
                j += 1
            slots = np.ascontiguousarray(sg.src_slot[i:j], dtype=np.int64)
            lp, up = src_fronts[g]
            fg = plan.groups[g]
            if len(slots) == fg.batch and np.array_equal(
                    slots, np.arange(fg.batch)):
                lp, up = jnp.asarray(lp), jnp.asarray(up)   # whole group
            else:
                lp = jnp.asarray(lp)[slots]
                up = jnp.asarray(up)[slots]
            if (fg.w, fg.u) != (sg.w, sg.u):
                lp, up = _pad_panels(lp, up, fg.w, fg.u, sg.w, sg.u)
            parts_l.append(lp)
            parts_u.append(up)
            i = j
        if len(parts_l) == 1:
            return parts_l[0], parts_u[0]
        return (jnp.concatenate(parts_l, axis=0),
                jnp.concatenate(parts_u, axis=0))

    @property
    def _invs(self):
        """Batched diagonal-block inverses (DiagInv), computed lazily on
        the first NON-transpose solve — transpose sweeps never read them,
        so a trans-only solver must not pay the inversion compiles or
        pin the inverse buffers in HBM."""
        if self._invs_cached is None:
            if self.diag_inv:
                self._invs_cached = [
                    call("diag_inv", f"solve_diag_inv b{grp.batch} w{grp.w}",
                         _diag_inv_kernel(grp.w,
                                          str(jnp.dtype(self.fact.dtype)),
                                          self.trsm_leaf, self.gemm_prec),
                         jnp.asarray(lp))
                    for (grp, _, _, _), (lp, _) in zip(self._groups,
                                                       self.fronts)]
            else:
                self._invs_cached = [(None, None)] * len(self._groups)
        return self._invs_cached

    def _fused_fns(self):
        """The fused forward and backward sweep programs of this
        solver's plan and settings (:func:`_fused_kernels`)."""
        return _fused_kernels(self._meta, self.n + 1, self.diag_inv,
                              self.trsm_leaf, self.gemm_prec)

    def _fused_trans_fns(self, conj):
        """The fused transpose pair (:func:`_fused_trans_kernels`)."""
        return _fused_trans_kernels(self._meta, self.n + 1, bool(conj),
                                    self.trsm_leaf, self.gemm_prec)

    def _run_sweeps(self, rhs, sweeps):
        """Shared solve scaffolding: map the request's nrhs onto the
        closed bucket set (column-chunking past the cap), pad each chunk
        into an (n+1, kb) buffer (slot n is the OOB dump row), run
        sweeps(x, lsum, kb) -> x per chunk, then unpad — one copy for
        the plain and transpose paths.  Executed-vs-structural flops
        (shape padding × nrhs padding) are reported on the kernel span
        and latched on ``last_solve_stats`` — the solve path's honesty
        telemetry, matching the factor path's."""
        tracer = get_tracer()
        squeeze = rhs.ndim == 1
        r2 = rhs[:, None] if squeeze else rhs
        k = r2.shape[1]
        chunks = chunk_nrhs(k, self.splan.nrhs_bucket_set)
        kb_total = sum(b for _, _, b in chunks)
        dt = jnp.dtype(self.fact.dtype)
        structural = self.splan.flops_per_rhs * k
        executed = self.splan.executed_flops_per_rhs * kb_total
        stats = {"nrhs": k, "padded_nrhs": kb_total,
                 "chunks": len(chunks),
                 "solve_flops": structural, "executed_flops": executed,
                 "padding_factor": round(executed / max(structural, 1.0),
                                         4)}
        nonfinite_cols: list = []
        out = np.empty((self.n, k), dtype=dt)
        d2h_s, d2h_bytes = 0.0, 0
        with tracer.span("device-solve", cat="kernel", n=self.n, nrhs=k,
                         padded_nrhs=kb_total, chunks=len(chunks),
                         fused=self.fused, n_groups=len(self._groups),
                         schedule=self.splan.schedule,
                         solve_flops=structural, executed_flops=executed,
                         padding_factor=stats["padding_factor"],
                         dtype=str(dt)) as sp, tally() as programs:
            for lo, hi, kb in chunks:
                pad = np.zeros((self.n + 1, kb), dtype=dt)
                pad[:self.n, :hi - lo] = r2[:, lo:hi]
                if self.mesh is not None:
                    # replicated over the global mesh: every process
                    # supplies the same host array, every process can
                    # read the result locally
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as P)
                    rep = NamedSharding(self.mesh, P(None, None))
                    if self._replicate is None:
                        # cached: a fresh lambda per solve would miss
                        # jax's trace cache on every IR correction solve.
                        # The input re-shard buffer is dead after the
                        # call — donate it so the replication aliases
                        # instead of doubling the (n+1, kb) footprint
                        # per chunk (slulint SLU111)
                        self._replicate = jax.jit(lambda a: a,
                                                  out_shardings=rep,
                                                  donate_argnums=(0,))
                    x = jax.device_put(pad, rep)
                    lsum = jax.device_put(np.zeros_like(pad), rep)
                    x = sweeps(x, lsum, kb)
                    # normalize whatever sharding GSPMD inferred back to
                    # fully replicated so np.asarray below is
                    # process-local
                    x = self._replicate(x)
                else:
                    x = jnp.asarray(pad)
                    lsum = jnp.zeros_like(x)
                    x = sweeps(x, lsum, kb)
                t0 = time.perf_counter()
                res = np.asarray(jax.block_until_ready(x))[:self.n,
                                                           :hi - lo]
                d2h_s += time.perf_counter() - t0
                d2h_bytes += int(res.nbytes)
                # per-column finiteness probe on the sweep output: the
                # serving tier's poisoned-request isolation needs to
                # know WHICH columns broke, not just that one did (one
                # all-reduce pass when healthy, per-column only on the
                # failure path)
                if not np.isfinite(res).all():
                    fin = np.isfinite(res).all(axis=0)
                    nonfinite_cols.extend(
                        int(lo + j) for j in np.nonzero(~fin)[0])
                out[:, lo:hi] = res
            if tracer.enabled:
                # the solution's D2H pull (the only factor-sized data
                # that ever crosses the boundary per solve)
                tracer.complete("solve-d2h", "comm",
                                time.perf_counter() - d2h_s, d2h_s,
                                op="d2h", bytes=d2h_bytes)
            # whether this solve reused its sweep programs or built them
            sp.set(program_hits=programs.hits,
                   program_misses=programs.misses)
        stats["finite"] = not nonfinite_cols
        stats["nonfinite_cols"] = nonfinite_cols
        if nonfinite_cols and tracer.enabled:
            tracer.complete("solve-probe", "verify", time.perf_counter(),
                            0.0, nonfinite=len(nonfinite_cols))
        self.last_solve_stats = stats
        return out[:, 0] if squeeze else out

    def solve_trans(self, rhs: np.ndarray, conj: bool = False) -> np.ndarray:
        """Solve (L·U)ᵀ x = rhs (or (L·U)ᴴ with conj) on the device —
        Mᵀ = Uᵀ·Lᵀ through the same factors (the reference's trans_t,
        superlu_defs.h:628-657; host twin: trisolve.lu_solve_trans).
        Respects the same fused/streamed guard as solve()."""
        fact = self.fact
        n1 = self.n + 1
        dt = jnp.dtype(fact.dtype)
        conj = bool(conj)
        leaf = self.trsm_leaf

        def sweeps(x, lsum, kb):
            if self.fused:
                fwd, bwd = self._fused_trans_fns(conj)
                idx = [(firsts, rows, ws)
                       for _, firsts, rows, ws in self._groups]
                _audit_sweep(f"fusedT-fwd n{self.n} k{kb}", fwd,
                             (x, lsum, self.fronts, idx), dead=(0, 1))
                x, lsum = call("solve", f"solve_fwd_trans n{self.n} k{kb}",
                               fwd, x, lsum, self.fronts, idx)
                _audit_sweep(f"fusedT-bwd n{self.n} k{kb}", bwd,
                             (x, self.fronts, idx), dead=(0,))
                return call("solve", f"solve_bwd_trans n{self.n} k{kb}",
                            bwd, x, self.fronts, idx)
            # Uᵀ forward, sweep batches ascending
            for (grp, firsts, rows, ws), (lp, up) in zip(
                    self._groups, self.fronts):
                kern = _fwd_trans_kernel(grp.batch, grp.m, grp.w, grp.u,
                                         kb, n1, str(dt), conj, leaf,
                                         self.gemm_prec)
                label = (f"b{grp.batch} m{grp.m} w{grp.w} u{grp.u} "
                         f"k{kb} n{self.n}")
                _audit_sweep(f"fwdT {label}", kern,
                             (lp, up, x, lsum, firsts, rows, ws), dead=(2, 3))
                x, lsum = call("solve", f"solve_fwd_trans {label}", kern,
                               lp, up, x, lsum, firsts, rows, ws)
            # Lᵀ backward, descending
            for (grp, firsts, rows, ws), (lp, up) in zip(
                    reversed(self._groups), reversed(self.fronts)):
                kern = _bwd_trans_kernel(grp.batch, grp.m, grp.w, grp.u,
                                         kb, n1, str(dt), conj, leaf,
                                         self.gemm_prec)
                label = (f"b{grp.batch} m{grp.m} w{grp.w} u{grp.u} "
                         f"k{kb} n{self.n}")
                _audit_sweep(f"bwdT {label}", kern,
                             (lp, x, firsts, rows, ws), dead=(1,))
                x = call("solve", f"solve_bwd_trans {label}", kern,
                         lp, x, firsts, rows, ws)
            return x

        return self._run_sweeps(rhs, sweeps)

    def solve(self, rhs: np.ndarray) -> np.ndarray:
        """rhs (n,) or (n, k) in permuted labeling -> solution, same shape."""
        fact = self.fact
        n1 = self.n + 1
        dt = jnp.dtype(fact.dtype)
        use_inv = self.diag_inv
        leaf = self.trsm_leaf

        def sweeps(x, lsum, kb):
            if self.fused:
                fwd, bwd = self._fused_fns()
                idx = [(firsts, rows, ws)
                       for _, firsts, rows, ws in self._groups]
                invs = self._invs
                _audit_sweep(f"fused-fwd n{self.n} k{kb}", fwd,
                             (x, lsum, self.fronts, idx, invs), dead=(0, 1))
                x, lsum = call("solve", f"solve_fwd n{self.n} k{kb}", fwd,
                               x, lsum, self.fronts, idx, invs)
                _audit_sweep(f"fused-bwd n{self.n} k{kb}", bwd,
                             (x, self.fronts, idx, invs), dead=(0,))
                return call("solve", f"solve_bwd n{self.n} k{kb}", bwd,
                            x, self.fronts, idx, invs)
            # forward in dispatch order (topological: every descendant's
            # batch precedes its ancestors' under either scheduler)
            for (grp, firsts, rows, ws), (lp, up), (linv, _) in zip(
                    self._groups, self.fronts, self._invs):
                kern = _fwd_kernel(grp.batch, grp.m, grp.w, grp.u, kb, n1,
                                   str(dt), use_inv, leaf, self.gemm_prec)
                args = ((lp, x, lsum, firsts, rows, ws, linv) if use_inv
                        else (lp, x, lsum, firsts, rows, ws))
                label = (f"b{grp.batch} m{grp.m} w{grp.w} u{grp.u} "
                         f"k{kb} n{self.n}")
                _audit_sweep(f"fwd {label}", kern, args, dead=(1, 2))
                x, lsum = call("solve", f"solve_fwd {label}", kern, *args)
            # backward, descending
            for (grp, firsts, rows, ws), (lp, up), (_, uinv) in zip(
                    reversed(self._groups), reversed(self.fronts),
                    reversed(self._invs)):
                kern = _bwd_kernel(grp.batch, grp.m, grp.w, grp.u, kb, n1,
                                   str(dt), use_inv, leaf, self.gemm_prec)
                args = ((lp, up, x, firsts, rows, ws, uinv) if use_inv
                        else (lp, up, x, firsts, rows, ws))
                label = (f"b{grp.batch} m{grp.m} w{grp.w} u{grp.u} "
                         f"k{kb} n{self.n}")
                _audit_sweep(f"bwd {label}", kern, args, dead=(2,))
                x = call("solve", f"solve_bwd {label}", kern, *args)
            return x

        return self._run_sweeps(rhs, sweeps)

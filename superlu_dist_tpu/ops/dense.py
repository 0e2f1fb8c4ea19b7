"""Dense supernodal kernels — the TPU offload boundary.

This layer replaces the reference's BLAS seam (CBLAS fallback / vendor BLAS
/ cuBLAS, SURVEY.md L1): the panel factorization dger/dtrsm loop
(pdgstrf2_trsm, SRC/pdgstrf2.c:140-318), the U-row triangular solves
(pdgstrs2_omp, :771), and the Schur-complement GEMM
(dSchCompUdt-2Ddynamic.c:566) all become one *batched partial factorization
of padded dense fronts*, vmapped over a level's worth of supernodes and
compiled by XLA onto the MXU.

Everything is static-shape: fronts are padded to bucket sizes (M total, W
pivot columns), with identity columns in the pivot-block padding so the
unpivoted LU passes through them untouched.  Tiny pivots are replaced by
±sqrt(eps)·‖A‖ exactly like the reference's GESP (pdgstrf2.c:218-232,
option ReplaceTinyPivot), and counted.

Layout of a factored front F (M×M, pivot width W, real sizes w ≤ W,
u ≤ M−W):
    F[:W, :W]   packed LU of the diagonal block (unit-lower L11 + U11)
    F[W:, :W]   L21 = A21·U11⁻¹   (real data in rows W..W+u)
    F[:W, W:]   U12 = L11⁻¹·A12
    F[W:, W:]   Schur complement S = A22 − L21·U12 (scattered to the pool)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from superlu_dist_tpu.utils.options import env_str

_UNROLL = 16   # panel width factored by the unrolled column loop

# ---------------------------------------------------------------------------
# The GEMM precision ladder (docs/PERFORMANCE.md, throughput ladder).
#
# Every Schur-update GEMM in the factor hot path runs at one named tier,
# ordered fastest/least-accurate first:
#
#   bf16     inputs cast to bfloat16, products accumulated in f32
#            (preferred_element_type pins the accumulator) — the MXU's
#            native rate (~6x the HIGHEST baseline on v5e)
#   default  native inputs, lax.Precision.DEFAULT — single-pass bf16 on
#            TPU (the tensorfloat analog: reduced-mantissa inputs, f32
#            accumulate); identical math to f32 on the CPU backend
#   f32      lax.Precision.HIGH — 3-pass bf16, ~full f32-mantissa products
#   highest  lax.Precision.HIGHEST — 6-pass, the exact-f32 baseline
#
# Reduced tiers are made safe to gamble by the gemm-precision escalation
# rung (drivers/gssvx._escalate): a delivered componentwise BERR above
# the gate refactors the SAME skeleton at the next-higher tier, so the
# fast path is default-on without ever degrading delivered accuracy.
# The resolved tier is threaded as an explicit parameter (like the
# pivot-kernel choice) — cached jitted factories key on it and the env
# read stays in the uncached wrappers (slulint SLU102/SLU104/SLU105).
# ---------------------------------------------------------------------------

GEMM_PREC_LADDER = ("bf16", "default", "f32", "highest")

_TIER_LAX = {"default": lax.Precision.DEFAULT,
             "f32": lax.Precision.HIGH,
             "highest": lax.Precision.HIGHEST}

#: legacy SLU_TPU_PRECISION pass-count names -> ladder tiers (an
#: explicitly-set legacy knob keeps meaning what it always meant)
_LEGACY_TIER_MAP = {"default": "default", "high": "f32",
                    "highest": "highest"}


def gemm_precision(name: str | None = None) -> str:
    """Resolve the Schur-GEMM precision tier.

    ``name`` (an Options.gemm_prec value) wins when given; otherwise the
    registered ``SLU_TPU_GEMM_PREC`` knob, then an explicitly-set legacy
    ``SLU_TPU_PRECISION``, then the ladder default ``"default"`` (the
    tensorfloat-analog fast path — identical math to f32 on CPU).  Read
    only from uncached factory wrappers; the result is part of every
    kernel cache key (slulint SLU105 discipline)."""
    if name is None or not str(name).strip():
        name = env_str("SLU_TPU_GEMM_PREC").strip().lower()
        if not name:
            legacy = env_str("SLU_TPU_PRECISION", default="").strip().lower()
            name = _LEGACY_TIER_MAP.get(legacy, "default")
    name = str(name).strip().lower()
    if name not in GEMM_PREC_LADDER:
        raise ValueError(f"SLU_TPU_GEMM_PREC={name!r} — expected one of "
                         f"{list(GEMM_PREC_LADDER)}")
    return name


def next_gemm_precision(tier: str, backend: str | None = None) -> str | None:
    """The next-higher ladder tier that actually CHANGES the arithmetic
    on ``backend``, or None at the top — the escalation rung's step
    function (drivers/gssvx._escalate).

    XLA:CPU executes every ``lax.Precision`` identically (full f32/f64
    products), so there the only real boundary is the bf16 input cast:
    escalating default→f32→highest on CPU would refactor three times
    for bitwise-identical factors, burning the ladder's rung budget on
    no-ops before the dtype escalation gets its turn."""
    if backend is None:
        backend = jax.default_backend()
    i = GEMM_PREC_LADDER.index(tier)
    if i + 1 >= len(GEMM_PREC_LADDER):
        return None
    if backend == "cpu" and tier != "bf16":
        return None          # default/f32/highest coincide on CPU
    return GEMM_PREC_LADDER[i + 1]


def resolve_gemm_tier(prec: str, dtype) -> str:
    """The tier :func:`gemm` will actually RUN for ``dtype`` operands.

    One degrade exists: complex operands have no bf16 carrier, so the
    ``bf16`` tier resolves to ``default`` instead of silently dropping
    imaginary precision.  Callers that record or escalate the tier
    (kernel spans, the BERR ladder) must report THIS value — a trace
    must never show a tier the arithmetic didn't use."""
    if prec == "bf16" and jnp.issubdtype(jnp.result_type(dtype),
                                         jnp.complexfloating):
        return "default"
    return prec


def gemm(a, b, prec: str = "highest"):
    """One ladder-tier batched matmul: the single matmul wrapper every
    Schur-update GEMM in the factor path (and the blocked-TRSM
    off-diagonal GEMMs, solve/device._trsm) routes through.

    ``preferred_element_type`` is pinned to the accumulator dtype on
    every tier, so reduced-INPUT GEMMs still accumulate at f32 (or the
    operands' own width) — the mixed-precision contract the BERR gate
    assumes.  The bf16 tier casts real inputs to bfloat16 and casts the
    f32-accumulated product back; complex operands degrade per
    :func:`resolve_gemm_tier` (asserted, not silently assumed)."""
    out_dt = jnp.result_type(a.dtype, b.dtype)
    # 16-bit-float factor dtypes still accumulate at f32 — pinning the
    # accumulator to bf16 would be a silent accuracy regression
    acc_dt = (jnp.float32 if out_dt in (jnp.bfloat16, jnp.float16)
              else out_dt)
    tier = resolve_gemm_tier(prec, out_dt)
    if tier == "bf16":
        assert not jnp.issubdtype(out_dt, jnp.complexfloating), \
            "bf16 tier on complex operands must resolve to 'default'"
        r = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       precision=lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)
        return r.astype(out_dt)
    r = jnp.matmul(a, b, precision=_TIER_LAX[tier],
                   preferred_element_type=acc_dt)
    return r.astype(out_dt) if acc_dt != out_dt else r


def _fix_pivot(piv, thresh):
    """GESP tiny-pivot replacement: piv -> phase(piv)·thresh if |piv|<thresh."""
    ap = jnp.abs(piv)
    safe = jnp.where(ap == 0, jnp.ones_like(ap), ap)
    unit = jnp.where(ap == 0, jnp.ones_like(piv), piv / safe.astype(piv.dtype))
    tiny = ap < thresh
    return jnp.where(tiny, unit * thresh.astype(piv.dtype), piv), tiny.astype(jnp.int32)


def _lu_masked(a, thresh):
    """Unpivoted LU of a small block — scatter-free masked formulation.

    Each step is masked selects + a full-matrix rank-1 update + `where`
    masks: no scatter/dynamic-update ops at all.  That matters twice on
    TPU: (a) masked dense updates vectorize on the VPU where scatters
    serialize, and (b) XLA's SPMD partitioner miscompiles vmapped
    scatter-updates whose minor dim gets sharded (observed jax 0.9.0), so
    the factorization core must stay scatter-free to be mesh-shardable.
    The ~3× extra flops of full-width updates are negligible next to the
    Schur GEMMs.

    Row/column/pivot extraction uses elementwise masked reductions rather
    than one-hot dot products: a dot_general here would route through the
    MXU at default precision (bf16 inputs on TPU), truncating the pivot row
    and the pivot value itself every elimination step.

    Returns (packed LU, tiny: (k,) int32 per-column tiny-pivot flags) —
    per-column so callers can mask out identity-padding columns.
    """
    k = a.shape[0]
    idx = jnp.arange(k)

    def step(i, carry):
        a, flags = carry
        sel = idx == i
        e = sel.astype(a.dtype)
        row_i = jnp.sum(a * e[:, None], axis=0)    # row i
        col_i = jnp.sum(a * e[None, :], axis=1)    # column i
        piv_raw = jnp.sum(row_i * e)
        piv, tiny = _fix_pivot(piv_raw, thresh)
        below = (idx > i)
        l = jnp.where(below, col_i / piv, jnp.zeros_like(col_i))
        u = jnp.where(below, row_i, jnp.zeros_like(row_i))   # cols > i
        a = a - l[:, None] * u[None, :]
        # write multipliers + fixed pivot into column i
        new_col = jnp.where(below, l, col_i) + (piv - piv_raw) * e
        cur_col = jnp.sum(a * e[None, :], axis=1)
        a = a + (new_col - cur_col)[:, None] * e[None, :]
        return a, flags + tiny * sel.astype(jnp.int32)

    return jax.lax.fori_loop(0, k, step, (a, jnp.zeros(k, jnp.int32)))


def lu_nopivot(a, thresh, gemm_prec: str = "highest"):
    """Blocked-recursive unpivoted LU with tiny-pivot replacement.

    Static shapes throughout; the trailing update is a single GEMM per
    recursion level, which is where XLA maps onto the MXU.
    ``gemm_prec`` is the caller-resolved ladder tier (gemm_precision) —
    threaded, never read from env here (slulint SLU102).

    Returns (packed LU, tiny: (n,) int32 per-column tiny-pivot flags).
    """
    n = a.shape[0]
    if n <= _UNROLL:
        return _lu_masked(a, thresh)
    h = max(_UNROLL, (n // 2 + _UNROLL - 1) // _UNROLL * _UNROLL)
    h = min(h, n - 1)
    a11, a12 = a[:h, :h], a[:h, h:]
    a21, a22 = a[h:, :h], a[h:, h:]
    f11, c1 = lu_nopivot(a11, thresh, gemm_prec)
    u12 = solve_triangular(f11, a12, lower=True, unit_diagonal=True)
    l21 = solve_triangular(f11, a21.T, trans=1, lower=False).T
    s = a22 - gemm(l21, u12, gemm_prec)
    f22, c2 = lu_nopivot(s, thresh, gemm_prec)
    top = jnp.concatenate([f11, u12], axis=1)
    bot = jnp.concatenate([l21, f22], axis=1)
    return jnp.concatenate([top, bot], axis=0), jnp.concatenate([c1, c2])


_PANEL_BLOCK = 128   # outer panel width of the blocked right-looking LU


def pivot_kernel() -> str:
    """Resolve SLU_TPU_PIVOT_KERNEL (validated like _precision).  Read at
    trace time — executors bake the choice into their cached programs, so
    callers that cache jitted kernels must include this name in their
    cache key (stream._kernel, factor.get_executor do)."""
    name = env_str("SLU_TPU_PIVOT_KERNEL").strip().lower()
    if name not in ("blocked", "recursive"):
        raise ValueError(f"SLU_TPU_PIVOT_KERNEL={name!r} — expected "
                         f"'blocked' or 'recursive'")
    return name


def _blocked_partial_factor(f, thresh, w, gemm_prec: str = "highest"):
    """Right-looking blocked partial LU of one front — compile-bounded.

    The recursive formulation (lu_nopivot) emits O(w/16) distinct
    triangular_solve/GEMM shapes; the TPU compiler takes minutes per
    kernel on wide panels (w ≥ 400 observed >8 min), which round 2 hit
    as the "compile wall".
    This version is the classic blocked getrf as ONE fori_loop whose body
    has a single static shape: eliminate a PB-wide panel with masked
    rank-1 steps, one (PB,PB)⁻¹·(PB,M) unit-lower triangular solve for
    the U rows, one (M,PB)×(PB,M) trailing GEMM — the MXU-shaped k=PB
    update that carries all the flops (the reference's aggregated Schur
    GEMM, dSchCompUdt-2Ddynamic.c:566-578, fused with the panel factor).
    Compile cost is O(1) in w; executed flops ≈ 2·M²·w (full-width
    trailing updates — the masked-padding trade noted in _lu_masked).

    Columns j ≥ w and identity-padding columns behave as unit pivots with
    zero multipliers, so the loop runs a static ceil(w/PB) panels and the
    final matrix carries packed LU in [:w,:w], L21 below, U12 right, and
    the Schur complement in [w:,w:] — same layout as partial_front_factor.

    NOTE: uses dynamic_slice/dynamic_update_slice on the column axis, so
    it must NOT be used with a column-sharded front (XLA SPMD handles
    that poorly); group_partial_factor keeps the recursive path when
    shardings are requested.

    Returns (packed front (M_ext→M, M), tiny flags (w,)).
    """
    m = f.shape[0]
    pb = min(_PANEL_BLOCK, -(-w // 16) * 16)
    nsteps = -(-w // pb)
    # shrink the panel so nsteps*pb hugs w: e.g. w=136 would otherwise
    # run 2×128 panels and pad the front to 256 columns — up to ~4× the
    # area in solves/GEMMs for wide-pivot small-U buckets
    pb = -(-(-(-w // nsteps)) // 16) * 16
    nsteps = -(-w // pb)
    m_ext = max(m, nsteps * pb)
    if m_ext > m:
        # zero padding; padded columns are never eliminated (j >= w ->
        # inactive) and padded rows stay zero throughout
        f = jnp.pad(f, ((0, m_ext - m), (0, m_ext - m)))
    rows = jnp.arange(m_ext)
    cols_pb = jnp.arange(pb)
    zero = jnp.zeros((), f.dtype)
    one = jnp.ones((), f.dtype)

    def inner(jj, carry):
        panel, flags, j0 = carry
        j = j0 + jj                                   # global column
        active = (j < w)
        col = lax.dynamic_index_in_dim(panel, jj, axis=1, keepdims=False)
        rowj = lax.dynamic_index_in_dim(panel, j, axis=0, keepdims=False)
        piv_raw = lax.dynamic_index_in_dim(rowj, jj, axis=0, keepdims=False)
        piv, tiny = _fix_pivot(piv_raw, thresh)
        piv = jnp.where(active, piv, one)
        below = rows > j
        l = jnp.where(below & active, col / piv, zero)
        urow = jnp.where((cols_pb > jj) & active, rowj, zero)
        panel = panel - l[:, None] * urow[None, :]
        # write the multipliers + fixed pivot back into column jj —
        # inactive columns (j >= w: Schur region / identity padding) keep
        # their values untouched
        newcol = jnp.where(active,
                           jnp.where(below, l, col)
                           + (piv - piv_raw) * (rows == j), col)
        e = (cols_pb == jj).astype(f.dtype)
        cur = lax.dynamic_index_in_dim(panel, jj, axis=1, keepdims=False)
        panel = panel + (newcol - cur)[:, None] * e[None, :]
        flags = flags + tiny * active.astype(jnp.int32) * (
            jnp.arange(w) == j).astype(jnp.int32)
        return panel, flags, j0

    def outer(p, carry):
        a, flags = carry
        j0 = p * pb
        panel = lax.dynamic_slice(a, (0, j0), (m_ext, pb))
        panel, flags, _ = lax.fori_loop(0, pb, inner, (panel, flags, j0))
        a = lax.dynamic_update_slice(a, panel, (0, j0))
        # U rows: solve unit-L11 against the columns right of the panel
        l11 = lax.dynamic_slice(panel, (j0, 0), (pb, pb))
        rtop = lax.dynamic_slice(a, (j0, 0), (pb, m_ext))
        right = rows[None, :] >= j0 + pb              # (1, m_ext) col mask
        u12 = solve_triangular(l11, jnp.where(right, rtop, zero),
                               lower=True, unit_diagonal=True)
        rowact = (j0 + jnp.arange(pb)) < w            # pivot rows only
        u12 = jnp.where(rowact[:, None] & right, u12, zero)
        a = lax.dynamic_update_slice(
            a, jnp.where(rowact[:, None] & right, u12, rtop), (j0, 0))
        # trailing update: every non-pivot row — rows below the panel AND
        # Schur rows (>= w) that fall inside the panel's row range —
        # against all columns to the right
        lpan = jnp.where(((rows >= j0 + pb) | (rows >= w))[:, None],
                         panel, zero)
        a = a - gemm(lpan, u12, gemm_prec)
        return a, flags

    a, flags = lax.fori_loop(0, nsteps, outer,
                             (f, jnp.zeros(w, jnp.int32)))
    return a[:m, :m], flags


def partial_front_factor(f, thresh, w, gemm_prec: str = "highest"):
    """Factor the leading w columns of one front; see module docstring."""
    m = f.shape[0]
    f11, count = lu_nopivot(f[:w, :w], thresh, gemm_prec)
    if w == m:
        return f11, count
    u12 = solve_triangular(f11, f[:w, w:], lower=True, unit_diagonal=True)
    l21 = solve_triangular(f11, f[w:, :w].T, trans=1, lower=False).T
    s = f[w:, w:] - gemm(l21, u12, gemm_prec)
    top = jnp.concatenate([f11, u12], axis=1)
    bot = jnp.concatenate([l21, s], axis=1)
    return jnp.concatenate([top, bot], axis=0), count


def group_partial_factor(fronts, thresh, w, front_sharding=None,
                         pivot_sharding=None, pivot="blocked",
                         gemm_prec="highest"):
    """Partial factorization of a batch of fronts with explicit shardings.

    Group-level formulation of partial_front_factor: the pivot-block LU is
    latency-bound (unrolled column loop) and runs replicated along the
    "panel" mesh axis (pivot_sharding), while the trailing triangular
    solves and the Schur GEMM — where the flops are (reference
    dSchCompUdt-2Ddynamic.c:566) — are pure batched matmuls that partition
    cleanly over the 2D mesh (front_sharding).  Note: the scatter-style
    pivot loop must NOT be sharded along its last dim — XLA's SPMD
    partitioner miscompiles vmapped scatter-updates with a sharded minor
    dimension (observed on jax 0.9.0), and splitting a tiny LU across
    chips would be latency-dominated anyway.

    Returns (lpanel (B,m,w), upanel (B,w,u), schur (B,u,u), tiny (B,w)).
    lpanel stacks the packed diagonal block (L11 unit-lower + U11) over
    L21; upanel is U12.  The Schur block is returned separately — the
    caller scatters it into the update pool and then drops it, so the
    stored factors are only the n_L + n_U panels the solves read (the
    reference likewise keeps L in Lnzval_bc_ptr and U in Unzval_br_ptr and
    never stores the eliminated A22, superlu_ddefs.h:97-183).
    """
    from jax.lax import with_sharding_constraint as wsc
    m = fronts.shape[-1]
    b = fronts.shape[0]
    # `pivot`/`gemm_prec` are the caller-resolved SLU_TPU_PIVOT_KERNEL /
    # SLU_TPU_GEMM_PREC choices: this function runs inside cached jitted
    # factories, so the env reads must happen in the (uncached) factory
    # wrappers that put both in their cache keys — never here at trace
    # time (slulint SLU105)
    if (front_sharding is None and pivot_sharding is None
            and pivot == "blocked"):
        # unsharded: the compile-bounded blocked kernel (see
        # _blocked_partial_factor).  Sharded runs keep the recursive
        # path — its scatter-free masked core is what the SPMD
        # partitioner handles.
        packed, tiny = jax.vmap(
            lambda x: _blocked_partial_factor(x, thresh, w,
                                              gemm_prec))(fronts)
        return (packed[:, :, :w], packed[:, :w, w:],
                packed[:, w:, w:], tiny)
    f11_in = fronts[:, :w, :w]
    if pivot_sharding is not None:
        f11_in = wsc(f11_in, pivot_sharding)
    f11, tiny = jax.vmap(lambda x: lu_nopivot(x, thresh, gemm_prec))(f11_in)
    if w == m:
        if pivot_sharding is not None:
            f11 = wsc(f11, pivot_sharding)
        u = 0
        return f11, jnp.zeros((b, w, u), fronts.dtype), \
            jnp.zeros((b, u, u), fronts.dtype), tiny
    a12 = fronts[:, :w, w:]
    a21 = fronts[:, w:, :w]
    a22 = fronts[:, w:, w:]
    u12 = jax.vmap(lambda l, b_: solve_triangular(l, b_, lower=True,
                                                  unit_diagonal=True))(f11, a12)
    l21 = jax.vmap(lambda u_, b_: solve_triangular(u_, b_.T, trans=1,
                                                   lower=False).T)(f11, a21)
    s = a22 - gemm(l21, u12, gemm_prec)
    if front_sharding is not None:
        s = wsc(s, front_sharding)
    lpanel = jnp.concatenate([f11, l21], axis=1)
    if front_sharding is not None:
        lpanel = wsc(lpanel, front_sharding)
    return lpanel, u12, s, tiny


def make_front_kernel(m: int, w: int, dtype: str):
    """Jitted batched front factorization for bucket shape (M=m, W=w).

    Returns fn(F: (B, m, m), thresh) -> (F_packed: (B, m, m), tiny: int32).
    Cached per (m, w, dtype, pivot kernel, gemm tier); batch size
    participates in jit's own cache.  Honors SLU_TPU_PIVOT_KERNEL and
    SLU_TPU_GEMM_PREC like the executors.
    """
    return _make_front_kernel(m, w, dtype, pivot_kernel(), gemm_precision())


@functools.lru_cache(maxsize=None)
def _make_front_kernel(m: int, w: int, dtype: str, pivot: str,
                       gemm_prec: str = "highest"):
    if pivot == "blocked":
        def kernel(fronts, thresh):
            outs, flags = jax.vmap(
                lambda f: _blocked_partial_factor(f, thresh, w,
                                                  gemm_prec))(fronts)
            return outs, jnp.sum(flags)
    else:
        def kernel(fronts, thresh):
            outs, counts = jax.vmap(
                lambda f: partial_front_factor(f, thresh, w,
                                               gemm_prec))(fronts)
            return outs, jnp.sum(counts)

    return jax.jit(kernel)

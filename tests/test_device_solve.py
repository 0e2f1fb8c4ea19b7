"""DeviceSolver coverage on the CPU backend.

The device triangular solve (solve/device.py, the pdgstrs analog,
SRC/pdgstrs.c:838) normally only runs on accelerators; constructing it
directly here keeps it under CI on the CPU backend so regressions surface
before real TPU hardware (the reference's analog: GPU-vs-CPU path diff
tests, SURVEY.md §4).
"""

import numpy as np
import pytest

from superlu_dist_tpu.drivers.gssvx import gssvx
from superlu_dist_tpu.models.gallery import (
    poisson2d, random_sparse, convection_diffusion_2d)
from superlu_dist_tpu.solve.device import DeviceSolver
from superlu_dist_tpu.solve.trisolve import lu_solve
from superlu_dist_tpu.utils.options import Options, IterRefine


def _factor(a, **opt_kw):
    opts = Options(iter_refine=IterRefine.NOREFINE, **opt_kw)
    n = a.n_rows
    b = np.ones(n, dtype=a.data.dtype)
    x, lu, stats, info = gssvx(opts, a, b)
    assert info == 0
    return lu


@pytest.mark.parametrize("nrhs", [1, 3, 1024])
@pytest.mark.parametrize("diag_inv", [False, True])
def test_device_solver_matches_host(nrhs, diag_inv):
    a = poisson2d(9)
    lu = _factor(a)
    rng = np.random.default_rng(5)
    d = rng.standard_normal((a.n_rows, nrhs))
    d = d[:, 0] if nrhs == 1 else d
    ds = DeviceSolver(lu.numeric, diag_inv=diag_inv)
    got = ds.solve(d)
    want = lu_solve(lu.numeric, d)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)
    # nrhs-padding honesty (the executed-vs-structural fix): the padded
    # width is the bucketed one and executed flops cover structural
    st = ds.last_solve_stats
    from superlu_dist_tpu.solve.plan import bucket_nrhs
    assert st["nrhs"] == nrhs
    assert st["padded_nrhs"] == bucket_nrhs(nrhs,
                                            ds.splan.nrhs_bucket_set)
    assert st["executed_flops"] >= st["solve_flops"] > 0


def test_device_solver_chunked_past_bucket_cap(monkeypatch):
    """nrhs past SLU_TPU_SOLVE_NRHS_MAX column-chunks (the bounded
    compile set): results reassemble exactly against the host solve."""
    monkeypatch.setenv("SLU_TPU_SOLVE_NRHS_MAX", "32")
    a = poisson2d(9)
    lu = _factor(a)
    d = np.random.default_rng(6).standard_normal((a.n_rows, 70))
    ds = DeviceSolver(lu.numeric)
    got = ds.solve(d)
    assert ds.last_solve_stats["chunks"] == 3          # 32 + 32 + 6->8
    assert ds.last_solve_stats["padded_nrhs"] == 32 + 32 + 8
    want = lu_solve(lu.numeric, d)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("dtype", ["float32", "float64", "complex128",
                                   "df64"])
def test_device_solver_dtype_matrix(dtype):
    """Device-vs-host agreement across the factor dtype tiers: f32
    (the TPU default), f64, c128 (the z-twin), and the emulated-double
    df64 path (whose recombined f64 factors are host-resident — the
    solver consumes them as-is)."""
    a = poisson2d(8)
    if dtype == "complex128":
        vals = a.data + 1j * np.random.default_rng(4).standard_normal(a.nnz)
        a = type(a)(a.n_rows, a.n_cols, a.indptr, a.indices, vals)
        lu = _factor(a)
    elif dtype == "df64":
        lu = _factor(a, factor_dtype="df64")
    else:
        lu = _factor(a, factor_dtype=dtype)
    rng = np.random.default_rng(9)
    d = rng.standard_normal((a.n_rows, 3))
    if dtype == "complex128":
        d = d + 1j * rng.standard_normal(d.shape)
    got = DeviceSolver(lu.numeric).solve(d)
    want = lu_solve(lu.numeric, d)
    tol = dict(rtol=2e-4, atol=1e-6) if dtype == "float32" \
        else dict(rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(got, want, **tol)
    # transpose path through the same factors
    from superlu_dist_tpu.solve.trisolve import lu_solve_trans
    conj = dtype == "complex128"
    got_t = DeviceSolver(lu.numeric).solve_trans(d, conj=conj)
    want_t = lu_solve_trans(lu.numeric, d, conj=conj)
    np.testing.assert_allclose(got_t, want_t, **tol)


def test_diag_inv_through_driver():
    """Options.diag_inv (reference DiagInv, util.c:397-401) end-to-end."""
    a = poisson2d(10)
    n = a.n_rows
    xt = np.random.default_rng(2).standard_normal(n)
    b = a.matvec(xt)
    x, lu, stats, info = gssvx(Options(diag_inv=True), a, b)
    assert info == 0
    lu.solve_path = "device"   # force the device path on the CPU backend
    lu.dev_solver = None
    x2 = lu.solve_factored(b)
    assert lu.dev_solver.diag_inv
    np.testing.assert_allclose(x2, x, rtol=1e-7, atol=1e-9)


@pytest.mark.slow
def test_device_solver_padded_buckets():
    # irregular sizes force fronts with padded widths/batches
    a = random_sparse(73, density=0.06, seed=3)
    lu = _factor(a, min_bucket=8, bucket_growth=1.5, relax=4, max_supernode=12)
    rng = np.random.default_rng(7)
    d = rng.standard_normal((a.n_rows, 2))
    got = DeviceSolver(lu.numeric).solve(d)
    want = lu_solve(lu.numeric, d)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)


@pytest.mark.slow
def test_device_solver_through_driver_path():
    # full driver solve (permutations + scalings) with the device path
    # forced on the CPU backend
    a = convection_diffusion_2d(10)
    n = a.n_rows
    xtrue = np.random.default_rng(0).standard_normal(n)
    b = a.matvec(xtrue)
    x, lu, stats, info = gssvx(Options(), a, b)
    assert info == 0
    lu.solve_path = "device"
    lu.dev_solver = None
    x_dev = lu.solve_factored(b)
    np.testing.assert_allclose(x_dev, x, rtol=1e-7, atol=1e-9)


def test_device_solver_complex():
    """c128 factors through the device solve path (the pzgstrs z-twin
    capability, SRC/pzgstrs.c) — CPU backend here, same kernels on TPU."""
    from superlu_dist_tpu.models.gallery import random_sparse
    a = random_sparse(48, density=0.08, seed=11)
    vals = a.data + 1j * np.random.default_rng(4).standard_normal(a.nnz)
    ac = type(a)(a.n_rows, a.n_cols, a.indptr, a.indices, vals)
    lu = _factor(ac)
    rng = np.random.default_rng(8)
    d = rng.standard_normal((ac.n_rows, 2)) + 1j * rng.standard_normal(
        (ac.n_rows, 2))
    got = DeviceSolver(lu.numeric).solve(d)
    want = lu_solve(lu.numeric, d)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)


@pytest.mark.slow
def test_fused_and_streamed_solve_agree():
    """fused=True (one program per sweep) must equal the per-group
    dispatch path bit-for-bit."""
    a = poisson2d(11)
    lu = _factor(a)
    rng = np.random.default_rng(9)
    d = rng.standard_normal((a.n_rows, 2))
    x_stream = DeviceSolver(lu.numeric, fused=False).solve(d)
    x_fused = DeviceSolver(lu.numeric, fused=True).solve(d)
    np.testing.assert_array_equal(x_fused, x_stream)
    want = lu_solve(lu.numeric, d)
    np.testing.assert_allclose(x_fused, want, rtol=1e-9, atol=1e-11)


_PLAIN = ["solve_bwd", "solve_fwd"]
_TRANS = ["solve_bwd_trans", "solve_fwd_trans"]


@pytest.mark.parametrize("settings,builds", [
    ({}, []),
    ({"gemm_prec": "highest"}, _PLAIN + _TRANS),
    ({"diag_inv": True}, _PLAIN),
    ({"trsm_leaf": 8}, _PLAIN + _TRANS),
    ({"conj": True}, _TRANS)],
    ids=["same", "gemm_prec", "diag_inv", "trsm_leaf", "conj"])
def test_refactor_reuses_sweep_programs(fresh_solve_programs, settings,
                                        builds):
    """A SamePattern refactor's new solver runs the fused sweeps the
    first factorization's solver built: no ``solve`` build, each answer
    its own factorization's.  A solver whose settings the traced sweeps
    read (GEMM tier, DiagInv, TRSM leaf, conjugation) builds anew."""
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    from superlu_dist_tpu.solve.trisolve import lu_solve_trans
    from superlu_dist_tpu.utils.options import Fact
    a = poisson2d(9)
    lu = _factor(a)
    d = np.random.default_rng(8).standard_normal(a.n_rows)
    first = lu.numeric
    ds = DeviceSolver(first)
    np.testing.assert_allclose(ds.solve(d), lu_solve(first, d),
                               rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(ds.solve_trans(d), lu_solve_trans(first, d),
                               rtol=1e-9, atol=1e-11)
    diag = np.repeat(np.arange(a.n_rows), np.diff(a.indptr)) == a.indices
    a2 = type(a)(a.n_rows, a.n_cols, a.indptr, a.indices,
                 a.data * np.where(diag, 1.5, 1.0))
    x, lu2, stats, info = gssvx(
        Options(fact=Fact.SamePattern_SameRowPerm,
                iter_refine=IterRefine.NOREFINE), a2, d, lu=lu)
    assert info == 0
    second = lu2.numeric
    assert second is not first and second.plan is first.plan
    settings = dict(settings)
    conj = settings.pop("conj", False)
    ds2 = DeviceSolver(second, **settings)
    m0 = COMPILE_STATS.marker()
    got = ds2.solve(d)
    got_t = ds2.solve_trans(d, conj=conj)
    built = [r.key.split()[0] for r in COMPILE_STATS.records[m0:]
             if r.site == "solve"]
    assert sorted(built) == sorted(builds)
    # a new setting is a new program, not a re-trace of the old one
    assert ((ds2._fused_fns() is ds._fused_fns())
            == ("solve_fwd" not in builds))
    assert ((ds2._fused_trans_fns(conj) is ds._fused_trans_fns(False))
            == ("solve_fwd_trans" not in builds))
    np.testing.assert_allclose(got, lu_solve(second, d),
                               rtol=1e-9, atol=1e-11)
    np.testing.assert_allclose(got_t, lu_solve_trans(second, d, conj=conj),
                               rtol=1e-9, atol=1e-11)
    assert not np.allclose(got, lu_solve(first, d))


@pytest.mark.parametrize("conj", [False, True])
def test_device_solve_trans_matches_host(conj):
    """Device transpose sweeps (UT then LT, the trans_t path) vs the host
    lu_solve_trans — real and complex."""
    from superlu_dist_tpu.solve.trisolve import lu_solve_trans
    from superlu_dist_tpu.models.gallery import random_sparse
    a = random_sparse(60, density=0.08, seed=13)
    if conj:
        vals = a.data + 1j * np.random.default_rng(3).standard_normal(a.nnz)
        a = type(a)(a.n_rows, a.n_cols, a.indptr, a.indices, vals)
    lu = _factor(a)
    rng = np.random.default_rng(17)
    d = rng.standard_normal((a.n_rows, 2))
    if conj:
        d = d + 1j * rng.standard_normal(d.shape)
    got = DeviceSolver(lu.numeric).solve_trans(d, conj=conj)
    want = lu_solve_trans(lu.numeric, d, conj=conj)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-10)


def test_trans_through_driver_device_path():
    """Full AᵀX=B driver solve with the device path forced on CPU."""
    from superlu_dist_tpu.utils.options import Trans
    from superlu_dist_tpu.models.gallery import convection_diffusion_2d
    a = convection_diffusion_2d(9)
    n = a.n_rows
    xt = np.random.default_rng(4).standard_normal(n)
    b = a.transpose().matvec(xt)
    x, lu, stats, info = gssvx(Options(trans=Trans.TRANS), a, b)
    assert info == 0
    lu.solve_path = "device"
    lu.dev_solver = None
    x_dev = lu.solve_factored_trans(b)
    r = np.linalg.norm(b - a.transpose().matvec(x_dev)) / np.linalg.norm(b)
    assert r < 1e-8, r


@pytest.mark.slow
def test_trans_streamed_matches_fused():
    from superlu_dist_tpu.solve.trisolve import lu_solve_trans
    a = poisson2d(10)
    lu = _factor(a)
    d = np.random.default_rng(21).standard_normal((a.n_rows, 2))
    got_f = DeviceSolver(lu.numeric, fused=True).solve_trans(d)
    got_s = DeviceSolver(lu.numeric, fused=False).solve_trans(d)
    np.testing.assert_array_equal(got_f, got_s)
    want = lu_solve_trans(lu.numeric, d)
    np.testing.assert_allclose(got_f, want, rtol=1e-9, atol=1e-11)


@pytest.mark.slow
def test_wide_rhs_batch():
    """nrhs well past the bucket boundary (the reference sweeps nrhs and
    its solve batches Linv GEMMs for large nrhs — SURVEY.md §7 hard-part
    5); both solver paths, 40 columns."""
    a = poisson2d(10)
    lu = _factor(a)
    rng = np.random.default_rng(31)
    d = rng.standard_normal((a.n_rows, 40))
    got = DeviceSolver(lu.numeric).solve(d)
    want = lu_solve(lu.numeric, d)
    assert got.shape == want.shape == (a.n_rows, 40)
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-11)

"""Native host-analysis library vs the Python specification.

The C++ kernels (native/slu_host.cpp) must produce bit-identical analysis
results to the Python implementations they accelerate — same etree, same
postorder, same supernode partition/rows, same matching + scalings.  The
Python code is the oracle (the reference's analog: serial vs parallel
symbolic producing identical structures).
"""

import numpy as np
import pytest

from superlu_dist_tpu import native
from superlu_dist_tpu.models.gallery import (
    poisson2d, random_sparse, convection_diffusion_2d)
from superlu_dist_tpu.sparse.formats import SparseCSR, symmetrize_pattern
from superlu_dist_tpu.ordering.etree import etree_symmetric, postorder
from superlu_dist_tpu.ordering.dissection import bfs_nd
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def _cases():
    return [
        symmetrize_pattern(poisson2d(15)),
        symmetrize_pattern(random_sparse(150, density=0.04, seed=1)),
        symmetrize_pattern(convection_diffusion_2d(12)),
    ]


def test_etree_and_postorder_match_python():
    for sym in _cases():
        n = sym.n_rows
        pn = native.etree(n, sym.indptr, sym.indices)
        pp = etree_symmetric(n, sym.indptr, sym.indices)
        assert np.array_equal(pn, pp)
        assert np.array_equal(native.postorder(pp), postorder(pp))


@pytest.mark.parametrize("relax,maxs", [(1, 8), (8, 32), (20, 256)])
def test_symbolic_matches_python(relax, maxs, monkeypatch):
    for sym in _cases():
        n = sym.n_rows
        order = np.arange(n)
        # Python-only run (native disabled via env knob)
        monkeypatch.setenv("SLU_TPU_NO_NATIVE", "1")
        native._tried, native._lib = False, None
        sf_py = symbolic_factorize(sym, order, relax=relax, max_supernode=maxs)
        monkeypatch.delenv("SLU_TPU_NO_NATIVE")
        native._tried, native._lib = False, None
        sf_nat = symbolic_factorize(sym, order, relax=relax, max_supernode=maxs)
        assert np.array_equal(sf_py.sn_start, sf_nat.sn_start)
        assert np.array_equal(sf_py.sn_parent, sf_nat.sn_parent)
        assert np.array_equal(sf_py.sn_level, sf_nat.sn_level)
        assert sf_py.nnz_L == sf_nat.nnz_L
        for rp, rn in zip(sf_py.sn_rows, sf_nat.sn_rows):
            assert np.array_equal(rp, rn)


def test_mc64_matches_python():
    from superlu_dist_tpu.rowperm import matching as m
    for seed in range(3):
        a = random_sparse(90, density=0.07, seed=seed)
        import superlu_dist_tpu.native as nat
        csc = a.tocsc()
        cm, u, v = nat.mc64(a.n_rows, csc.indptr, csc.indices,
                            np.abs(csc.data))
        # python path forced
        import os
        os.environ["SLU_TPU_NO_NATIVE"] = "1"
        nat._tried, nat._lib = False, None
        try:
            ro, r, c = m.maximum_product_matching(a)
        finally:
            del os.environ["SLU_TPU_NO_NATIVE"]
            nat._tried, nat._lib = False, None
        assert np.array_equal(cm, ro)
        colmax = np.zeros(a.n_rows)
        cols = np.repeat(np.arange(a.n_rows), np.diff(csc.indptr))
        np.maximum.at(colmax, cols, np.abs(csc.data))
        np.testing.assert_allclose(np.exp(np.clip(v, -700, 700)), r,
                                   rtol=1e-10)
        np.testing.assert_allclose(
            np.exp(np.clip(u - np.log(colmax), -700, 700)), c, rtol=1e-10)


def _per_column_fill(sf):
    """Per-column below-diagonal fill counts — invariant across valid
    supernode partitions of the same (zero-fill-merged) structure."""
    last = sf.sn_start[1:] - 1
    out = np.empty(sf.n, dtype=np.int64)
    for s in range(sf.n_supernodes):
        for j in range(int(sf.sn_start[s]), int(sf.sn_start[s + 1])):
            out[j] = (last[s] - j) + len(sf.sn_rows[s])
    return out


@pytest.mark.parametrize("nthreads", [2, 4])
def test_threaded_symbolic_same_fill(nthreads):
    """The threaded symbolic (symbfact_dist analog) must produce the same
    per-column fill as serial; the supernode partition may differ only by
    boundary chain merges."""
    from superlu_dist_tpu.models.gallery import poisson3d
    for sym in _cases() + [symmetrize_pattern(poisson3d(8))]:
        n = sym.n_rows
        order = np.arange(n)
        ser = symbolic_factorize(sym, order, relax=4, max_supernode=64,
                                 amalg_tol=0)
        par = symbolic_factorize(sym, order, relax=4, max_supernode=64,
                                 nthreads=nthreads, amalg_tol=0)
        assert np.array_equal(_per_column_fill(ser), _per_column_fill(par))
        assert par.nnz_L >= ser.nnz_L   # fewer merges => never less padding


def test_threaded_symbolic_end_to_end():
    """Solve through a threaded-symbolic factorization."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.utils.options import Options
    from superlu_dist_tpu.models.gallery import poisson2d
    import os
    a = poisson2d(12)
    xt = np.random.default_rng(0).standard_normal(a.n_rows)
    b = a.matvec(xt)
    os.environ["SLU_TPU_SYMB_THREADS"] = "4"
    try:
        x, lu, stats, info = gssvx(Options(), a, b)
    finally:
        del os.environ["SLU_TPU_SYMB_THREADS"]
    assert info == 0
    np.testing.assert_allclose(x, xt, rtol=1e-8, atol=1e-8)


def test_mmd_matches_python():
    """Native exact-MD must match the Python oracle bit-for-bit (same
    algorithm, same tie-breaking)."""
    import os
    from superlu_dist_tpu.ordering import minimum_degree as md_mod
    for sym in _cases():
        n = sym.n_rows
        got = native.mmd(n, sym.indptr, sym.indices)
        os.environ["SLU_TPU_NO_NATIVE"] = "1"
        native._tried, native._lib = False, None
        try:
            want = md_mod.minimum_degree(n, sym.indptr, sym.indices)
        finally:
            del os.environ["SLU_TPU_NO_NATIVE"]
            native._tried, native._lib = False, None
        assert np.array_equal(got, want)


def test_mmd_scales_beyond_python():
    """The native MD must handle sizes the Python sets version cannot."""
    sym = symmetrize_pattern(poisson2d(45))       # n = 2025
    n = sym.n_rows
    order = native.mmd(n, sym.indptr, sym.indices)
    assert sorted(order) == list(range(n))
    sf = symbolic_factorize(sym, order, relax=1, max_supernode=64,
                            amalg_tol=0)
    nat = symbolic_factorize(sym, np.arange(n), relax=1, max_supernode=64,
                             amalg_tol=0)
    assert sf.nnz_L < 0.5 * nat.nnz_L             # real fill reduction


def test_mlnd_is_valid_permutation_and_beats_bfs():
    a = symmetrize_pattern(random_sparse(600, density=0.02, seed=4))
    n = a.n_rows
    order = native.mlnd(n, a.indptr, a.indices)
    assert sorted(order) == list(range(n))

    def fill(o):
        return symbolic_factorize(a, o, relax=1, max_supernode=64,
                                  amalg_tol=0).nnz_L

    # the multilevel ordering must clearly beat the BFS level-set fallback
    assert fill(order) < fill(bfs_nd(n, a.indptr, a.indices))


def test_mlnd_fill_quality_vs_scipy_colamd():
    """VERDICT r1 gate: fill within ~2x of scipy COLAMD on an irregular
    matrix (the reference's METIS_AT_PLUS_A quality bar)."""
    sp = pytest.importorskip("scipy.sparse")
    spl = pytest.importorskip("scipy.sparse.linalg")
    a0 = random_sparse(500, density=0.02, seed=11)
    sym = symmetrize_pattern(a0)
    n = sym.n_rows
    order = native.mlnd(n, sym.indptr, sym.indices)
    sf = symbolic_factorize(sym, order, relax=1, max_supernode=64,
                            amalg_tol=0)
    data = np.where(sym.data == 0, 1e-8, sym.data)
    A = sp.csr_matrix((data, sym.indices, sym.indptr), shape=(n, n)).tocsc()
    lu = spl.splu(A, permc_spec="COLAMD",
                  options=dict(SymmetricMode=False))
    assert sf.nnz_L <= 2.0 * lu.L.nnz, (sf.nnz_L, lu.L.nnz)



def test_library_named_by_source_hash():
    """The loaded library is the one built from the committed source:
    its file name carries the source's sha256, so a library left over
    from any other source is never picked up."""
    import hashlib
    import os
    with open(native._SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    path = native.lib_path()
    assert os.path.basename(path) == f"_slu_host-{digest}.so"
    native.require()
    assert os.path.exists(path)


def test_require_raises_when_library_unavailable(monkeypatch):
    """require() names why there is no library instead of degrading to
    the Python analysis; available() keeps the soft answer."""
    monkeypatch.setenv("SLU_TPU_NO_NATIVE", "1")
    native._tried, native._lib = False, None
    try:
        assert not native.available()
        with pytest.raises(RuntimeError, match="SLU_TPU_NO_NATIVE"):
            native.require()
    finally:
        monkeypatch.delenv("SLU_TPU_NO_NATIVE")
        native._tried, native._lib = False, None
    assert native.require() is not None

"""Time stepping on a plan whose groups batch many fronts.

A 2-D convection–diffusion plan puts half of the factor's work into
groups that vmap one front program over tens to hundreds of fronts (the
benchmark cell ``convdiff2d_768.timestep`` at n = 589,824 has 16 groups
of 64–633).  Here the same path runs on the CPU at a small size, through
the streamed executor the chip uses: one DOFACT, then three
``SamePattern_SameRowPerm`` refactors of A + s·diag(A), nrhs 1, float32
factors, each answer judged against float64 arithmetic on a known
solution by the numbers the benchmark's check compares (``ferr``, the
normwise ``berr``).
"""

import dataclasses

import numpy as np
import pytest

from superlu_dist_tpu.drivers.gssvx import gssvx
from superlu_dist_tpu.models.gallery import convection_diffusion_2d
from superlu_dist_tpu.sparse.formats import SparseCSR
from superlu_dist_tpu.utils.options import Fact, IterRefine, Options

#: (nx, blocking): a blocking small enough that nx = 64 yields a group of
#: at least 64 fronts, and the TPU blocking the benchmark runs with
BLOCKINGS = {
    "small": (64, dict(relax=16, max_supernode=64, min_bucket=8)),
    "tpu": (48, dict(relax=256, max_supernode=1024, min_bucket=32,
                     bucket_growth=1.3, amalg_tol=1.2)),
}

#: three of the timestep traffic's 16 stratum midpoints of [0, 1]
SHIFTS = (0.03125, 0.46875, 0.96875)

#: float64 refinement stops at a backward error of a few ulps (the
#: ladder's target), and ferr <= cond(A)·berr with cond(A) ~ 1e3 at
#: these sizes: the sound path reads ferr up to 6.5e-16 and berr up to
#: 1e-16 on both blockings.  The control (float32 residuals) stalls at
#: float32 rounding: ferr 2.6e-8 to 1.5e-7, berr 1.0e-8 to 5.3e-8.  Each
#: limit sits at least 100x from both.
FERR_TOL = 1e-10
BERR_TOL = 1e-12


def shifted(a, s):
    """A + s·diag(A) on A's pattern (the timestep traffic's A_k)."""
    rows = np.repeat(np.arange(a.n_rows), np.diff(a.indptr))
    vals = a.data.copy()
    vals[rows == a.indices] *= 1.0 + s
    return SparseCSR(a.n_rows, a.n_cols, a.indptr, a.indices, vals)


def errors(a, x, x_true, b):
    """Forward error against ``x_true`` and the normwise backward error,
    both in float64, as max-norms."""
    anorm = np.max(np.add.reduceat(np.abs(a.data), a.indptr[:-1]))
    r = b - a.matvec(x)
    return {"ferr": np.max(np.abs(x - x_true)) / np.max(np.abs(x_true)),
            "berr": np.max(np.abs(r))
            / (anorm * np.max(np.abs(x)) + np.max(np.abs(b)))}


@pytest.fixture(scope="module", params=sorted(BLOCKINGS))
def dofact(request):
    """One DOFACT on the streamed executor: (matrix, options, handle)."""
    nx, blocking = BLOCKINGS[request.param]
    a = convection_diffusion_2d(nx, beta=10.0)
    opts = Options(**blocking, factor_dtype="float32", executor="stream")
    b = a.matvec(np.ones(a.n_rows))
    _, lu, _, info = gssvx(opts, a, b)
    assert info == 0
    return request.param, a, opts, lu


def refactor_steps(a, opts, lu, seed=2**33 + 7):
    """Three SamePattern_SameRowPerm steps from ``lu``; each step's
    reference numbers."""
    opts = dataclasses.replace(opts, fact=Fact.SamePattern_SameRowPerm)
    rng = np.random.default_rng(seed)
    out = []
    for s in SHIFTS:
        ak = shifted(a, s)
        x_true = rng.standard_normal(a.n_rows)
        b = ak.matvec(x_true)
        x, lu_k, _, info = gssvx(opts, ak, b, lu=lu)
        assert info == 0
        assert lu_k.plan is lu.plan     # the pattern's plan is reused
        lu = lu_k
        out.append(errors(ak, np.asarray(x, dtype=np.float64), x_true, b))
    return out


def test_plan_batches_wide_groups(dofact):
    """The small blocking's plan holds a group of 64 or more fronts, the
    regime that carries over half the work at n = 589,824; the TPU blocking
    at nx = 48 batches fewer."""
    name, _, _, lu = dofact
    widest = max(g.batch for g in lu.plan.groups)
    assert widest >= (64 if name == "small" else 2)
    assert lu.numeric.executor == "StreamExecutor"


def test_refactor_steps_meet_reference(dofact):
    _, a, opts, lu = dofact
    for e in refactor_steps(a, opts, lu):
        assert e["ferr"] <= FERR_TOL and e["berr"] <= BERR_TOL, e


def test_single_precision_control_fails_reference(dofact):
    """The control of the limits above: the program's own float32-residual
    refinement, with the escalation ladder off, breaks one of them on
    every step."""
    _, a, opts, lu = dofact
    control = dataclasses.replace(
        opts, iter_refine=IterRefine.SLU_SINGLE,
        recovery=dataclasses.replace(opts.recovery, enabled=False))
    for e in refactor_steps(a, control, lu):
        assert e["ferr"] > FERR_TOL or e["berr"] > BERR_TOL, e

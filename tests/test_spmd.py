"""shard_map SPMD tier (parallel/spmd.py) — one compiled program per
factor group (and per solve-sweep bucket) over a real jax.Mesh.

The bitwise contract this suite pins (the PR 5 pattern): the SPMD
program's L/U factors AND solve vectors are bit-identical to the
single-device lockstep executors (fused/stream/mega are already bitwise
twins of each other) on the 8-virtual-device CPU mesh.  That is what
lets the TreeComm host-lockstep tier stand as the A/B reference: any
SPMD result can be re-derived lockstep and compared exactly.

Also covered: the mega executor runs its bucketed programs UNDER the
mesh (no auto-downgrade to stream; GSPMD re-tiling makes that an
allclose-class contract, see numeric/mega.py), plus auditor cleanliness
(SLU_TPU_VERIFY_SHARDING / SLU_TPU_VERIFY_PROGRAMS) and checkpoint-
frontier portability between the lockstep and SPMD entry points.
"""

import numpy as np
import pytest

import jax

from superlu_dist_tpu.models.gallery import (helmholtz_2d, hilbert,
                                             poisson2d,
                                             rank_deficient_arrowhead)
from superlu_dist_tpu.numeric.factor import get_executor, numeric_factorize
from superlu_dist_tpu.numeric.plan import build_plan
from superlu_dist_tpu.ordering.dispatch import get_perm_c
from superlu_dist_tpu.parallel.grid import gridinit
from superlu_dist_tpu.parallel.spmd import (SpmdFactorExecutor, SpmdSolver,
                                            spmd_mode)
from superlu_dist_tpu.solve.device import DeviceSolver
from superlu_dist_tpu.sparse.formats import symmetrize_pattern
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
from superlu_dist_tpu.utils.options import Options

pytestmark = pytest.mark.spmd


def _mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh (conftest XLA_FLAGS)")
    return gridinit(1, 8).mesh


def _analyzed(a, dtype="float64"):
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order)
    plan = build_plan(sf, schedule="dataflow")
    return plan, sym.data[sf.value_perm], a.norm_max()


def _bitwise_fronts(f0, f1):
    return all(np.array_equal(np.asarray(l0), np.asarray(l1))
               and np.array_equal(np.asarray(u0), np.asarray(u1))
               for (l0, u0), (l1, u1) in zip(f0.fronts, f1.fronts))


_GALLERY = [("poisson", lambda: poisson2d(16)),
            ("hilbert", lambda: hilbert(48)),
            ("arrowhead", lambda: rank_deficient_arrowhead(40))]


# ---------------------------------------------------------------------------
# bitwise L/U/X vs the lockstep executors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,make", _GALLERY)
def test_spmd_bitwise_vs_lockstep(name, make):
    """shard_map programs per factor group, bit-identical L/U to EVERY
    single-device lockstep executor, and bit-identical solve/solveT."""
    mesh = _mesh()
    plan, vals, anorm = _analyzed(make())
    fs = numeric_factorize(plan, vals, anorm, executor="spmd", mesh=mesh)
    for lockstep in ("fused", "stream", "mega"):
        f0 = numeric_factorize(plan, vals, anorm, executor=lockstep)
        assert _bitwise_fronts(f0, fs), (name, lockstep)
        assert f0.tiny_pivots == fs.tiny_pivots, (name, lockstep)
    rng = np.random.default_rng(7)
    rhs = rng.standard_normal((plan.n, 3))
    f0 = numeric_factorize(plan, vals, anorm, executor="fused")
    s0, s1 = DeviceSolver(f0), SpmdSolver(fs, mesh)
    assert np.array_equal(s0.solve(rhs), s1.solve(rhs)), name
    assert np.array_equal(s0.solve_trans(rhs), s1.solve_trans(rhs)), name


def test_spmd_bitwise_complex_conjugate_sweeps():
    """complex128 factor + Aᵀ/Aᴴ sweeps stay bitwise (the conjugate
    sweep bodies share operands with DeviceSolver exactly)."""
    mesh = _mesh()
    plan, vals, anorm = _analyzed(helmholtz_2d(10))
    f0 = numeric_factorize(plan, vals, anorm, executor="fused",
                           dtype="complex128")
    fs = numeric_factorize(plan, vals, anorm, executor="spmd", mesh=mesh,
                           dtype="complex128")
    assert _bitwise_fronts(f0, fs)
    rng = np.random.default_rng(3)
    rhs = (rng.standard_normal((plan.n, 2))
           + 1j * rng.standard_normal((plan.n, 2)))
    s0, s1 = DeviceSolver(f0), SpmdSolver(fs, mesh)
    assert np.array_equal(s0.solve(rhs), s1.solve(rhs))
    assert np.array_equal(s0.solve_trans(rhs), s1.solve_trans(rhs))
    assert np.array_equal(s0.solve_trans(rhs, conj=True),
                          s1.solve_trans(rhs, conj=True))


def test_spmd_is_one_program():
    """One shard_map program per distinct group shape, all compiled
    ahead of the first factorization's stream; a refactorization on
    the same executor builds none."""
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    mesh = _mesh()
    plan, vals, anorm = _analyzed(poisson2d(16))
    ex = get_executor(plan, "float64", executor="spmd", mesh=mesh)
    assert isinstance(ex, SpmdFactorExecutor)
    assert ex.granularity == "group"
    assert 1 <= ex.n_kernels <= len(plan.groups)
    for _ in range(2):
        mark = COMPILE_STATS.marker()
        f = numeric_factorize(plan, vals, anorm, executor="spmd", mesh=mesh)
        built = [r for r in COMPILE_STATS.records[mark:]
                 if r.site == "spmd.factor"]
        assert f.executor == "SpmdFactorExecutor"
        assert len(built) == (ex.n_kernels if _ == 0 else 0)


# ---------------------------------------------------------------------------
# dispatch rules: auto picks spmd on a mesh; knob + no-mesh downgrades
# ---------------------------------------------------------------------------

def test_auto_rule_and_knob(monkeypatch):
    mesh = _mesh()
    plan, _, _ = _analyzed(poisson2d(16))
    monkeypatch.delenv("SLU_TPU_SPMD", raising=False)
    assert spmd_mode() is True                # auto on single process
    ex = get_executor(plan, "float64", executor="auto", mesh=mesh)
    assert isinstance(ex, SpmdFactorExecutor)
    # the knob gates the auto rule off
    monkeypatch.setenv("SLU_TPU_SPMD", "0")
    assert spmd_mode() is False
    ex = get_executor(plan, "float64", executor="auto", mesh=mesh)
    assert not isinstance(ex, SpmdFactorExecutor)
    monkeypatch.setenv("SLU_TPU_SPMD", "1")
    assert spmd_mode() is True
    # no mesh / partitioned pool: explicit spmd downgrades to stream
    ex = get_executor(plan, "float64", executor="spmd", mesh=None)
    assert not isinstance(ex, SpmdFactorExecutor)
    ex = get_executor(plan, "float64", executor="spmd", mesh=mesh,
                      pool_partition=True)
    assert not isinstance(ex, SpmdFactorExecutor)


def test_knobs_registered():
    from superlu_dist_tpu.utils.options import KNOB_REGISTRY
    assert "SLU_TPU_SPMD" in KNOB_REGISTRY
    assert "BENCH_MESH" in KNOB_REGISTRY
    assert "spmd" in KNOB_REGISTRY["SLU_TPU_EXECUTOR"].choices


# ---------------------------------------------------------------------------
# mega runs UNDER the mesh (no downgrade)
# ---------------------------------------------------------------------------

def test_mega_under_mesh_no_downgrade():
    """MegaExecutor keeps its mesh instead of auto-downgrading to
    stream.  GSPMD re-tiles the batched triangular solves, so (exactly
    like stream-under-mesh) this is an allclose-class contract — the
    BITWISE mesh tier is the shard_map executor above."""
    from superlu_dist_tpu.numeric.mega import MegaExecutor
    mesh = _mesh()
    plan, vals, anorm = _analyzed(rank_deficient_arrowhead(40))
    ex = get_executor(plan, "float64", executor="mega", mesh=mesh)
    assert isinstance(ex, MegaExecutor)       # the old ValueError is gone
    assert ex.mesh is mesh
    f0 = numeric_factorize(plan, vals, anorm, executor="fused")
    f1 = numeric_factorize(plan, vals, anorm, executor="mega", mesh=mesh)
    assert f0.tiny_pivots == f1.tiny_pivots
    for (l0, u0), (l1, u1) in zip(f0.fronts, f1.fronts):
        for x0, x1 in ((l0, l1), (u0, u1)):
            assert np.allclose(np.asarray(x0), np.asarray(x1),
                               rtol=1e-12, atol=0)


# ---------------------------------------------------------------------------
# auditors: the SPMD programs are clean under the runtime verify tiers
# ---------------------------------------------------------------------------

def test_spmd_clean_under_runtime_auditors(monkeypatch):
    """SLU_TPU_VERIFY_SHARDING=1 + SLU_TPU_VERIFY_PROGRAMS=1: the
    factor program and the solve sweeps audit clean — 0 sharding
    findings (SLU119 replication included) and full donation coverage
    on declared-dead inputs."""
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    from superlu_dist_tpu.utils import programaudit
    mesh = _mesh()
    monkeypatch.setenv("SLU_TPU_VERIFY_SHARDING", "1")
    monkeypatch.setenv("SLU_TPU_VERIFY_PROGRAMS", "1")
    monkeypatch.delenv("SLU_TPU_VERIFY_DTYPES", raising=False)
    monkeypatch.delenv("SLU_TPU_MEM_BUDGET_BYTES", raising=False)
    programaudit._reset()
    with COMPILE_STATS._lock:
        saved = dict(COMPILE_STATS._audits)
        COMPILE_STATS._audits = {}
    try:
        plan, vals, anorm = _analyzed(poisson2d(16))
        f = numeric_factorize(plan, vals, anorm, executor="spmd",
                              mesh=mesh)
        s = SpmdSolver(f, mesh)
        s.solve(np.ones((plan.n, 2)))
        s.solve_trans(np.ones(plan.n))
        sh = programaudit.get_sharding_auditor()
        assert sh is not None and sh.findings == []
        pa = programaudit.get_auditor()
        assert pa is not None and not getattr(pa, "findings", [])
        blk = COMPILE_STATS.audit_block()
        assert blk["programs_sharding_audited"] >= 1
        assert blk["programs"] >= 1
        assert blk["donation_coverage_pct"] == 100.0
        # replicated traffic is PRICED, not forbidden: the tier
        # replicates the tiny pivot stacks / index vectors by design
        # (the bitwise contract) — what must hold is 0 findings above
        assert blk["replicated_bytes"] >= 0
    finally:
        programaudit._reset()
        with COMPILE_STATS._lock:
            COMPILE_STATS._audits = saved


# ---------------------------------------------------------------------------
# checkpoint frontiers are portable between the lockstep and SPMD tiers
# ---------------------------------------------------------------------------

def test_checkpoint_frontier_portable_lockstep_spmd(tmp_path):
    """A frontier written by an interrupted lockstep run resumes under
    an executor="spmd" request (and vice versa) to bitwise-identical
    factors: checkpointing has group boundaries only on the stream
    executor, so both entry points downgrade to it for the durable
    part, and the frontier format is shared."""
    from superlu_dist_tpu.testing.chaos import CountdownDeadline
    from superlu_dist_tpu.utils.errors import DeadlineExceededError
    _mesh()                                   # same env as the rest
    plan, vals, anorm = _analyzed(poisson2d(16))
    assert len(plan.groups) >= 4
    ref = numeric_factorize(plan, vals, anorm, executor="stream")
    # lockstep writes, spmd request resumes
    ck = str(tmp_path / "ck-lockstep")
    with pytest.raises(DeadlineExceededError):
        numeric_factorize(plan, vals, anorm, executor="stream",
                          ckpt_dir=ck, deadline=CountdownDeadline(3))
    res = numeric_factorize(plan, vals, anorm, executor="spmd",
                            resume_from=ck)
    assert res.resumed_groups == 3
    assert _bitwise_fronts(ref, res) and res.tiny_pivots == ref.tiny_pivots
    # spmd request writes (forced onto stream by the ckpt arm), lockstep
    # resumes
    ck2 = str(tmp_path / "ck-spmd")
    with pytest.raises(DeadlineExceededError):
        numeric_factorize(plan, vals, anorm, executor="spmd",
                          ckpt_dir=ck2, deadline=CountdownDeadline(3))
    res2 = numeric_factorize(plan, vals, anorm, executor="stream",
                             resume_from=ck2)
    assert res2.resumed_groups == 3
    assert _bitwise_fronts(ref, res2)

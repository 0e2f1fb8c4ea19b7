#!/usr/bin/env python
"""SPMD-equivalence gate (#20): the shard_map SPMD tier must be a
bitwise twin of the lockstep reference on the 8-virtual-device CPU mesh.

What it pins, on the gallery trio (poisson/hilbert/arrowhead):

* factor: SpmdFactorExecutor L/U and tiny-pivot count bit-identical to
  the single-device lockstep executors (fused and stream);
* solve: SpmdSolver x (and the transpose sweep) bit-identical to the
  lockstep DeviceSolver on the same factors;
* A/B reference: the demoted TreeComm host-lockstep driver (pgssvx,
  single rank) still produces the SAME bits as the single-process gssvx
  driver — the recovery-fallback chain SPMD results are gated against;
* compile discipline: one compiled program per distinct factor-group
  shape, all built by the first factorization and none by a second,
  with 100% donation coverage on declared-dead inputs and 0 sharding
  findings (SLU119 replication included) under the runtime auditors.

Exit 0 = pass.  One gate of scripts/ci_gates.sh; tens of seconds on
CPU.  Gate contract (shared with check_schedule_equiv.py and friends):
any regression raises/asserts, which exits non-zero with the
diagnostic on stderr.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
# runtime auditors ON for every program this gate builds
os.environ["SLU_TPU_VERIFY_PROGRAMS"] = "1"
os.environ["SLU_TPU_VERIFY_SHARDING"] = "1"

import numpy as np  # noqa: E402


def _analyzed(a):
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order)
    return (build_plan(sf, schedule="dataflow"), sym.data[sf.value_perm],
            a.norm_max())


def check(name, a, mesh):
    from superlu_dist_tpu.numeric.factor import (get_executor,
                                                 numeric_factorize)
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    from superlu_dist_tpu.parallel.spmd import (SpmdFactorExecutor,
                                                SpmdSolver)
    from superlu_dist_tpu.solve.device import DeviceSolver

    plan, vals, anorm = _analyzed(a)
    ex = get_executor(plan, "float64", executor="spmd", mesh=mesh)
    assert isinstance(ex, SpmdFactorExecutor), (
        f"{name}: spmd request downgraded to {type(ex).__name__}")
    assert 1 <= ex.n_kernels <= len(plan.groups), (
        f"{name}: {ex.n_kernels} factor programs for "
        f"{len(plan.groups)} groups")
    for want in (ex.n_kernels, 0):
        mark = COMPILE_STATS.marker()
        fs = numeric_factorize(plan, vals, anorm, executor="spmd",
                               mesh=mesh)
        built = [r for r in COMPILE_STATS.records[mark:]
                 if r.site == "spmd.factor"]
        assert len(built) == want, (
            f"{name}: {len(built)} spmd.factor compile records "
            f"(want {want})")
    for lockstep in ("fused", "stream"):
        f0 = numeric_factorize(plan, vals, anorm, executor=lockstep)
        assert f0.tiny_pivots == fs.tiny_pivots, (name, lockstep)
        for (l0, u0), (l1, u1) in zip(f0.fronts, fs.fronts):
            assert (np.array_equal(np.asarray(l0), np.asarray(l1))
                    and np.array_equal(np.asarray(u0), np.asarray(u1))), (
                f"{name}: SPMD L/U differ from lockstep {lockstep} "
                "(bitwise)")
    rng = np.random.default_rng(11)
    rhs = rng.standard_normal((plan.n, 3))
    f0 = numeric_factorize(plan, vals, anorm, executor="fused")
    s0, s1 = DeviceSolver(f0), SpmdSolver(fs, mesh)
    assert np.array_equal(s0.solve(rhs), s1.solve(rhs)), (
        f"{name}: SPMD solve differs from lockstep DeviceSolver")
    assert np.array_equal(s0.solve_trans(rhs), s1.solve_trans(rhs)), (
        f"{name}: SPMD transpose solve differs from lockstep")
    print(f"[spmd-equiv] {name}: OK ({ex.n_kernels} factor programs, "
          f"n={plan.n}, "
          f"L/U/x bitwise vs fused+stream lockstep)")


def check_treecomm_reference(a):
    """The demoted TreeComm tier stays a valid A/B reference: its x is
    bit-identical to the single-process gssvx driver's."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.parallel.dist import distribute_rows
    from superlu_dist_tpu.parallel.pgssvx import pgssvx
    from superlu_dist_tpu.parallel.treecomm import TreeComm
    from superlu_dist_tpu.utils.options import Options

    b = np.random.default_rng(5).standard_normal(a.n_rows)
    x0, _, _, info0 = gssvx(Options(), a, b.copy())
    name = f"/slu_spmd_gate_{os.getpid()}"
    with TreeComm(name, 1, 0, max_len=2048, create=True) as tc:
        x1, info1 = pgssvx(tc, Options(), distribute_rows(a, 1)[0],
                           b.copy())
    assert info0 == 0 and info1 == 0, (info0, info1)
    assert np.array_equal(np.asarray(x0).ravel(),
                          np.asarray(x1).ravel()), (
        "TreeComm A/B reference drifted from the lockstep gssvx driver")
    print("[spmd-equiv] TreeComm A/B reference: OK (x bitwise vs gssvx)")


def check_auditors_clean():
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    from superlu_dist_tpu.utils import programaudit

    sh = programaudit.get_sharding_auditor()
    assert sh is not None, "sharding auditor never armed"
    slu119 = [f for f in sh.findings if f.rule == "SLU119"]
    assert not sh.findings, (
        f"sharding findings on mesh programs ({len(slu119)} SLU119): "
        f"{sh.findings}")
    blk = COMPILE_STATS.audit_block()
    assert blk["programs"] >= 1 and blk["programs_sharding_audited"] >= 1
    assert blk["donation_coverage_pct"] == 100.0, (
        f"donation coverage {blk['donation_coverage_pct']}% (want 100%)")
    print(f"[spmd-equiv] auditors: OK ({blk['programs']} programs, "
          f"{blk['programs_sharding_audited']} sharding-audited, "
          f"donation {blk['donation_coverage_pct']}%, 0 findings)")


def main():
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    assert len(jax.devices()) >= 8, (
        f"need the 8-virtual-device mesh, got {len(jax.devices())}")
    from superlu_dist_tpu.models.gallery import (hilbert, poisson2d,
                                                 rank_deficient_arrowhead)
    from superlu_dist_tpu.parallel.grid import gridinit

    mesh = gridinit(1, 8).mesh
    check("poisson2d(16)", poisson2d(16), mesh)
    check("poisson2d(24)", poisson2d(24), mesh)
    check("hilbert(48)", hilbert(48), mesh)
    check("rank_deficient_arrowhead(40)", rank_deficient_arrowhead(40),
          mesh)
    check_treecomm_reference(poisson2d(16))
    check_auditors_clean()
    print("[spmd-equiv] all checks passed")


if __name__ == "__main__":
    main()

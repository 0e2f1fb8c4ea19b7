#!/usr/bin/env python
"""Precision-safety CI gate: the throughput ladder never delivers a
failing X.

BERR gate / escalation (docs/PERFORMANCE.md throughput
ladder): the bf16 GEMM tier on an ill-conditioned gallery matrix
(hilbert) must either pass the componentwise-BERR gate outright or
ESCALATE through the gemm-precision rung — the solve must come back
``converged`` with berr <= target and the ladder actions recorded in
the SolveReport.  Run twice: with iterative refinement (the default
path) and with IterRefine.NOREFINE (opting out of IR must not opt out
of the gate).

Gate contract (scripts/ci_gates.sh): exit 0 = pass, exit 1 = any
violation, diagnostics on stdout/stderr, runs under the shared
per-gate timeout.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def fail(msg: str):
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def phase_a() -> None:
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.models.gallery import hilbert
    from superlu_dist_tpu.utils.options import IterRefine, Options
    from superlu_dist_tpu.utils import tols

    a = hilbert(8)
    b = a.matvec(np.ones(a.n_rows))
    for label, opts in (
            ("refine", Options(gemm_prec="bf16", factor_dtype="float32")),
            ("norefine", Options(gemm_prec="bf16", factor_dtype="float32",
                                 iter_refine=IterRefine.NOREFINE))):
        x, lu, stats, info = gssvx(opts, a, b)
        rep = stats.solve_report
        if info != 0:
            fail(f"phase A [{label}]: info={info}")
        if not np.all(np.isfinite(np.asarray(x))):
            fail(f"phase A [{label}]: non-finite X delivered")
        if rep.berr is None or rep.target is None:
            fail(f"phase A [{label}]: no BERR gate was applied "
                 f"({rep.summary()})")
        # the delivered gate must BE the central model's target — a
        # driver that minted its own threshold would bypass utils/tols
        want_target = float(tols.berr_target(np.float64))
        if float(rep.target) != want_target:
            fail(f"phase A [{label}]: gate target {rep.target!r} is not "
                 f"tols.berr_target(float64) = {want_target!r} — the "
                 "driver drifted off the central tolerance model")
        if not rep.converged or rep.berr > rep.target:
            fail(f"phase A [{label}]: delivered berr {rep.berr:.3e} "
                 f"misses the gate {rep.target:.3e} and was still "
                 f"reported — {rep.summary()}")
        if not rep.rungs:
            fail(f"phase A [{label}]: bf16 on hilbert(8) met the f64 "
                 "gate without any ladder action — the gate matrix is "
                 "no longer exercising escalation; pick a harder one")
        print(f"  phase A [{label}]: berr {rep.berr:.3e} <= "
              f"{rep.target:.3e} via "
              f"{[f'{r.name}[{r.detail}]' for r in rep.rungs]} "
              f"(tier {rep.gemm_precision}, dtype {rep.factor_dtype})")


def main() -> int:
    print("== precision-safety gate ==")
    phase_a()
    print("precision-safety: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

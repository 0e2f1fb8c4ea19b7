#!/usr/bin/env python
"""Chip smoke test: the solver's main path, end to end, on a TPU.

Drives ``superlu_dist_tpu.gssvx`` the way README.md documents it — host
analysis, numeric factorization on the chip, device triangular solve,
f64 iterative refinement — on the 7-point 3-D Poisson matrix at
n = 48³ = 110,592 with the TPU blocking (relax 256, max supernode 1024,
min bucket 32, bucket growth 1.3, amalgamation 1.2).  The right-hand
sides are A·x_true for x_true drawn from ``--seed``; a second one is
solved through the stored factors (Fact=FACTORED) and a warm
same-pattern refactorization is timed.  Every answer must reach a
relative residual of at most 1e-12 and a forward error against x_true
of at most 1e-9.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --chips 4 [--seed N]

``--chips 4`` runs only the four-chip phase: the same matrix through the
SPMD tier on a 2x2 mesh (parallel/spmd.py), compared with a one-chip
factorization in the same process — both must meet the bounds, and
their unrefined solutions must agree within the f32 class of
utils/tols.py (not bitwise: the bitwise SPMD contract was derived on
XLA:CPU).

The last line of standard output is the contract line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, without the package beside this script, when the native
host library cannot be built, or when any check fails, the script exits
nonzero and prints no such line.  It runs in one process and starts no
child that touches JAX.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

NX = 48
RESID_BOUND = 1e-12      # relative residual ||b - A x|| / ||b||
FERR_BOUND = 1e-9        # forward error ||x - x_true||_inf / ||x_true||_inf
#: the TPU blocking; everything else is the library default
OPTIONS = dict(relax=256, max_supernode=1024, min_bucket=32,
               bucket_growth=1.3, amalg_tol=1.2)
#: ladder rungs that refactor (drivers/gssvx._escalate)
REFACTOR_RUNGS = ("gemm-precision", "hiprec-factors", "refactor-rescale")
ANALYSIS_PHASES = ("EQUIL", "ROWPERM", "COLPERM", "ETREE", "SYMBFACT",
                   "DIST")


def say(key, value):
    print(f"{key}: {value}", flush=True)


def fail(msg):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def errors(a, x, b, x_true):
    resid = float(np.linalg.norm(b - a.matvec(x)) / np.linalg.norm(b))
    ferr = float(np.max(np.abs(x - x_true)) / np.max(np.abs(x_true)))
    return resid, ferr


def rungs(stats):
    """The ladder rungs a gssvx call ran, and how many factorizations
    it made in all (its own plus one per refactoring rung)."""
    names = [r.name for r in stats.solve_report.rungs]
    return names, sum(n in REFACTOR_RUNGS for n in names)


def check(label, a, x, b, x_true):
    resid, ferr = errors(a, x, b, x_true)
    say(f"{label} relative residual", f"{resid:.3e}")
    say(f"{label} forward error", f"{ferr:.3e}")
    if not (resid <= RESID_BOUND and ferr <= FERR_BOUND):
        fail(f"{label}: residual {resid:.3e} (bound {RESID_BOUND:g}) or "
             f"forward error {ferr:.3e} (bound {FERR_BOUND:g})")


def one_chip(slu, a, rng):
    """gssvx DOFACT, a FACTORED second solve, a warm refactorization."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    from superlu_dist_tpu.solve.device import DeviceSolver
    n = a.n_rows
    x_true = rng.standard_normal(n)
    b = a.matvec(x_true)
    t0 = time.perf_counter()
    x, lu, stats, info = slu.gssvx(slu.Options(**OPTIONS), a, b)
    wall = time.perf_counter() - t0
    if info != 0:
        fail(f"gssvx info={info}")
    num = lu.numeric
    say("executor", f"{num.executor} ({len(lu.plan.groups)} groups, "
        f"factor dtype {num.dtype}, gemm tier {num.gemm_prec})")
    say("solve path", lu.solve_path)
    say("IR residual path", stats.ir_residual)
    say("host analysis seconds",
        f"{sum(stats.utime[p] for p in ANALYSIS_PHASES):.3f}")
    say("compile seconds, summed over kernels built in parallel",
        f"{stats.compile['seconds']:.3f} ({stats.compile['builds']} "
        f"builds, {stats.compile['persistent_hits']} persistent-cache "
        "hits)")
    say("factor seconds (first, compile included)",
        f"{stats.utime['FACT']:.3f}")
    say("gssvx seconds", f"{wall:.3f}")
    say("tiny pivots", stats.tiny_pivots)
    say("refinement steps", stats.refine_steps)
    say("ladder rungs", rungs(stats)[0])
    if num.executor != StreamExecutor.__name__:
        fail(f"expected the streamed executor on one chip, got "
             f"{num.executor}")
    if lu.solve_path != "device" or not isinstance(lu.dev_solver,
                                                   DeviceSolver):
        fail(f"solve path {lu.solve_path!r} is not the device solve")
    if stats.ir_residual != "device":
        fail(f"IR residual ran on {stats.ir_residual!r}, not the device")
    check("solve 1", a, x, b, x_true)

    x2_true = rng.standard_normal(n)
    b2 = a.matvec(x2_true)
    t0 = time.perf_counter()
    x2, lu, stats2, info = slu.gssvx(
        slu.Options(fact=slu.Fact.FACTORED, **OPTIONS), a, b2, lu=lu)
    say("FACTORED solve seconds", f"{time.perf_counter() - t0:.3f}")
    say("FACTORED refinement steps", stats2.refine_steps)
    say("FACTORED ladder rungs", rungs(stats2)[0])
    if info != 0 or stats2.ir_residual != "device":
        fail(f"FACTORED solve: info={info}, IR residual "
             f"{stats2.ir_residual!r}")
    check("solve 2 (FACTORED)", a, x2, b2, x2_true)

    x3, lu, stats3, info = slu.gssvx(
        slu.Options(fact=slu.Fact.SamePattern_SameRowPerm, **OPTIONS),
        a, b, lu=lu)
    names, refactors = rungs(stats3)
    say("factor seconds (warm refactor)", f"{stats3.utime['FACT']:.3f}")
    say("warm refactor factorizations", 1 + refactors)
    say("warm refactor ladder rungs", names)
    say("first factor minus warm refactor (compile wall)",
        f"{stats.utime['FACT'] - stats3.utime['FACT']:.3f}")
    say("warm refactor builds", stats3.compile["builds"])
    if info != 0:
        fail(f"refactor info={info}")
    check("solve 3 (refactor)", a, x3, b, x_true)


def four_chips(slu, a, rng, jax):
    """The SPMD tier on a 2x2 mesh against a one-chip factorization."""
    from superlu_dist_tpu.parallel.grid import gridinit
    from superlu_dist_tpu.parallel.spmd import SpmdFactorExecutor, SpmdSolver
    from superlu_dist_tpu.utils import tols
    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, JAX reports {len(devices)}")
    x_true = rng.standard_normal(a.n_rows)
    b = a.matvec(x_true)
    grid = gridinit(2, 2, devices[:4])
    t0 = time.perf_counter()
    x4, lu4, st4, info = slu.gssvx(slu.Options(**OPTIONS), a, b, grid=grid)
    say("spmd gssvx seconds", f"{time.perf_counter() - t0:.3f}")
    if info != 0:
        fail(f"spmd gssvx info={info}")
    say("spmd executor", lu4.numeric.executor)
    say("spmd solve", type(lu4.dev_solver).__name__)
    say("spmd IR residual path", st4.ir_residual)
    say("spmd compile seconds", f"{st4.compile['seconds']:.3f}")
    say("spmd factor seconds (compile included)",
        f"{st4.utime['FACT']:.3f}")
    say("spmd refinement steps", st4.refine_steps)
    say("spmd ladder rungs", rungs(st4)[0])
    if lu4.numeric.executor != SpmdFactorExecutor.__name__ or \
            not isinstance(lu4.dev_solver, SpmdSolver):
        fail("the 2x2 mesh did not run the SPMD factor and solve")
    if rungs(st4)[1]:
        fail("the SPMD answer rests on a refactorization, not the SPMD "
             "factors")
    check("spmd solve", a, x4, b, x_true)

    t0 = time.perf_counter()
    x1, lu1, st1, info = slu.gssvx(slu.Options(**OPTIONS), a, b)
    say("one-chip gssvx seconds", f"{time.perf_counter() - t0:.3f}")
    if info != 0:
        fail(f"one-chip gssvx info={info}")
    say("one-chip executor", lu1.numeric.executor)
    say("one-chip compile seconds", f"{st1.compile['seconds']:.3f}")
    say("one-chip ladder rungs", rungs(st1)[0])
    if rungs(st1)[1]:
        fail("the one-chip answer rests on a refactorization")
    check("one-chip solve", a, x1, b, x_true)

    # the unrefined solves expose the f32 factors themselves
    y4, y1 = lu4.solve_factored(b), lu1.solve_factored(b)
    diff = float(np.linalg.norm(y4 - y1) / np.linalg.norm(y1))
    bound = tols.SPMD_VS_ONE_CHIP_F32
    say("spmd vs one-chip unrefined solve difference",
        f"{diff:.3e} (bound {bound.describe()})")
    if not diff <= bound:
        fail(f"SPMD and one-chip factors disagree: {diff:.3e} > {bound}")
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices[:4]]
    say("peak bytes per device", peaks)
    if min(peaks) <= 0:
        fail(f"a device of the mesh did no work: {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "superlu_dist_tpu")):
        fail("the superlu_dist_tpu package is not beside this script")
    sys.path.insert(0, here)

    # the package sets the TPU compiler's flags, so it comes before JAX
    # touches the chip
    import superlu_dist_tpu as slu
    import jax
    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    say("device_kind", dev.device_kind)
    say("devices", len(jax.devices()))

    if not slu.TPU_PARALLEL_COMPILE:
        fail("JAX reached the TPU before the package set its flags")
    from superlu_dist_tpu import native
    native.require()                  # no fallback to the Python analysis
    from superlu_dist_tpu.models.gallery import poisson3d

    rng = np.random.default_rng(args.seed)
    a = poisson3d(NX)
    say("matrix", f"poisson3d({NX}), n={a.n_rows}, nnz={a.nnz}")
    if args.chips == 4:
        four_chips(slu, a, rng, jax)
    else:
        one_chip(slu, a, rng)
        say("peak_bytes_in_use", dev.memory_stats()["peak_bytes_in_use"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

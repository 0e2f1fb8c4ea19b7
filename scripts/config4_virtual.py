#!/usr/bin/env python
"""BASELINE config-4 class (3D Poisson, target n=1M) executed end-to-end
on the CPU backend at full problem size.

The point is EXECUTION at scale, not speed: n=1M's ~22 GB pool exceeds
one v5e chip's HBM, so a single chip cannot hold it.  Two modes (CONFIG4_MESH):

- "1" (default): single-device execution — the fastest path to a
  numeric-at-n=1M artifact (the pool partition is separately proven
  bit-equal at n=102,400, tests/test_pool_partition.py).  Artifact:
  docs/config4_virtual_n{n}_1dev.json.
- "RxC" (e.g. "4x2"): partitioned Schur pool over the R*C-device
  virtual mesh — the real multi-chip recipe (pool_partition +
  host-offloaded fronts); proves the sharded program compiles AND
  executes with the per-device pool share genuinely smaller than the
  whole (the no-rank-holds-the-whole-factor property, reference
  SRC/pddistribute.c:322).  On this 1-core box the collectives are
  hours of memcpy at n=1M.  Artifact: docs/config4_virtual_n{n}.json.

Env: CONFIG4_NX (default 100 -> n=1e6), CONFIG4_MESH (default "1"),
CONFIG4_DTYPE (default float32; a complex dtype, e.g. complex64, runs
the z-twin class — off-diagonals rotated into the complex plane — and
suffixes the artifact with the canonical dtype name, e.g.
docs/config4_virtual_n{n}_complex64_1dev.json).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import (REPO, cpu_session, parse_mesh_spec,  # noqa: E402
                     raise_collective_timeouts)


def main():
    raise_collective_timeouts()
    # parse + validate the mesh spec BEFORE anything expensive (and
    # before the device count is pinned)
    mesh_spec = os.environ.get("CONFIG4_MESH", "1")
    mesh_r, mesh_c, n_dev = parse_mesh_spec(mesh_spec)
    # x64: n=1M's Schur pool exceeds 2^31 entries — flat pool indices
    # need int64 (the reference's XSDK_INDEX_SIZE=64 build,
    # superlu_defs.h:85-88)
    jax = cpu_session(n_devices=n_dev)
    import jax.numpy as jnp

    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.utils.options import Options
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    from superlu_dist_tpu.numeric.factor import NumericFactorization
    from superlu_dist_tpu.drivers.gssvx import LUFactorization
    from superlu_dist_tpu.refine.ir import iterative_refinement
    from superlu_dist_tpu.parallel.grid import gridinit

    nx = int(os.environ.get("CONFIG4_NX", "100"))
    dtype = os.environ.get("CONFIG4_DTYPE", "float32")
    t_all = time.perf_counter()

    def log(msg):
        print(f"[config4 +{time.perf_counter() - t_all:8.1f}s] {msg}",
              file=sys.stderr, flush=True)

    a = poisson3d(nx)
    jdt = np.dtype(dtype)
    if np.issubdtype(jdt, np.complexfloating):
        # complex variant (the z-twin class, reference pzgstrf.c): rotate
        # the off-diagonals into the complex plane — non-Hermitian, same
        # pattern, still diagonally dominant
        from superlu_dist_tpu.sparse.formats import SparseCSR
        cdata = a.data.astype(np.complex128)
        off = a.indices != np.repeat(np.arange(a.n_rows),
                                     np.diff(a.indptr))
        cdata[off] *= (0.8 + 0.6j)
        a = SparseCSR(a.n_rows, a.n_cols, a.indptr, a.indices, cdata)
    n = a.n_rows
    log(f"matrix n={n} nnz={a.nnz} dtype={dtype}")

    t0 = time.perf_counter()
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order, relax=256, max_supernode=1024,
                            amalg_tol=1.2)
    plan = build_plan(sf, min_bucket=32, growth=1.3)
    t_analyze = time.perf_counter() - t0
    # complex MACs are ~4 real flops (reference z-routines count 6+2 per
    # mult+add); one real-equivalent figure feeds both the log and the
    # artifact so they cannot diverge
    flops_req = plan.flops * (4.0 if np.issubdtype(
        jdt, np.complexfloating) else 1.0)
    log(f"analysis {t_analyze:.1f}s; groups={len(plan.groups)} "
        f"pool={plan.pool_size * jdt.itemsize / 1e9:.1f} GB({dtype}) "
        f"flops={flops_req / 1e12:.2f} TF (real-equivalent)")

    if mesh_spec == "1":
        grid = None
        share = plan.pool_size
        ex = StreamExecutor(plan, dtype, offload="none")
    else:
        grid = gridinit(mesh_r, mesh_c)
        share = -(-plan.pool_size // grid.mesh.size)
        assert share < plan.pool_size, "pool must exceed one device share"
        ex = StreamExecutor(plan, dtype, mesh=grid.mesh,
                            pool_partition=True, offload="host")
    avals = np.asarray(sym.data[sf.value_perm], dtype=jdt)
    real_dt = np.finfo(jdt).dtype          # f32 for c64, identity for real
    eps = float(np.finfo(real_dt).eps)
    thresh = np.asarray(np.sqrt(eps) * a.norm_max(), real_dt)

    t0 = time.perf_counter()
    fronts, tiny = ex(jnp.asarray(avals), jnp.asarray(thresh))
    jax.block_until_ready(
        [lp for lp, _ in fronts if not isinstance(lp, np.ndarray)])
    t_factor = time.perf_counter() - t0
    log(f"factor (incl. compile) {t_factor:.1f}s  tiny={int(tiny)}")

    numeric = NumericFactorization(plan=plan, fronts=list(fronts),
                                   tiny_pivots=int(tiny),
                                   dtype=jnp.dtype(dtype))
    ones = np.ones(n)
    ident = np.arange(n, dtype=np.int64)
    lu = LUFactorization(n=n, options=Options(), equed="N", dr=ones,
                         dc=ones, r1=ones, c1=ones, row_order=ident,
                         col_order=None, sf=sf, plan=plan,
                         numeric=numeric, a=a)
    xt = np.random.default_rng(0).standard_normal(n)
    b = a.matvec(xt)
    t0 = time.perf_counter()
    x, steps = iterative_refinement(a, b, lu.solve_factored(b),
                                    lu.solve_factored)
    t_solve = time.perf_counter() - t0
    resid = float(np.linalg.norm(b - a.matvec(x))
                  / max(np.linalg.norm(b), 1e-300))
    log(f"solve+IR {t_solve:.1f}s  residual {resid:.2e}")

    rec = {"config": "4-virtual", "matrix": f"poisson3d nx={nx}", "n": n,
           "mesh": (f"{mesh_spec} virtual-cpu" if grid is not None
                    else "single-device cpu"),
           "pool_partition": grid is not None,
           "pool_bytes_total": plan.pool_size * jdt.itemsize,
           "pool_share_per_device": int(share) * jdt.itemsize,
           "dtype": jdt.name,
           "flops": flops_req,
           "analyze_seconds": round(t_analyze, 1),
           "factor_seconds_incl_compile": round(t_factor, 1),
           "solve_ir_seconds": round(t_solve, 1),
           "residual": resid, "tiny_pivots": int(tiny),
           "backend": ("cpu-virtual-mesh" if grid is not None
                       else "cpu-single-device"),
           "note": ("execution-at-scale artifact: single-core host, "
                    "timing not a perf claim"
                    + ("; the same sharded program runs on a real "
                       "multi-chip mesh" if grid is not None else ""))}
    # the unsuffixed path is reserved for the partitioned-mesh artifact
    # (the stronger claim); single-device runs carry the _1dev suffix
    suffix = "_1dev" if grid is None else ""
    if jdt != np.dtype(np.float32):
        suffix = f"_{jdt.name}" + suffix
    out = os.path.join(REPO, "docs", f"config4_virtual_n{n}{suffix}.json")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec), flush=True)
    from superlu_dist_tpu.utils import tols
    assert resid < tols.RESID_GATE_TIGHT, resid


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Prebake the mega executor's closed bucket set into the persistent
compile cache — the fleet warm-start primitive (ROADMAP item 2/4).

The mega executor (numeric/mega.py) compiles one program per CLOSED
shape bucket, and every program's shapes are canonical ladder rungs —
matrix-size-independent by construction.  That makes the persistent XLA
cache (utils/jaxcache.py) effectively keyed by the BUCKET SET rather
than the matrix: compile the set once, and every later process whose
plan maps onto the same buckets — a serving replica cold-starting via
``persist.from_bundle``, the bench, a resumed factorization — loads all
of its factor programs from disk and spends ~0 s in `factor-compile`.

This script builds that warm state ahead of need:

  warm_compile_cache.py [--nx N [N ...]] [--dtype D]
      Build the closed plan for poisson3d grids of edge N (default the
      gallery 16 32 48, the BENCH acceptance sizes) with the bench
      blocking, AOT-compile every bucket program into the persistent
      cache, and write a bucket-set warm marker per plan
      (jaxcache.mark_bucket_set_warm).  The cache is
      $JAX_COMPILATION_CACHE_DIR when set, else the checkout's
      .cache/jax.

  warm_compile_cache.py --bundle PATH [--dtype D]
      Same, but for the plan inside a persisted LU handle bundle
      (persist.load_lu) — warm the cache for exactly the matrix a
      serving fleet is about to load, without factoring anything.

Prints one JSON line per plan: bucket set digest, program count, and
the trace/lower/compile stage split (compile ≈ 0 when already warm).
Exit 0 always on success; any failure raises.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _closed_bench_plan(nx: int):
    """The bench blocking (bench.py CPU defaults) with the shape-key
    closure on — the kernel set the acceptance gallery measures."""
    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    a = poisson3d(nx)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym),
                            relax=128, max_supernode=256, amalg_tol=1.05)
    return build_plan(sf, min_bucket=16, growth=1.05, closed=True)


def warm_plan(plan, dtype: str) -> dict:
    """AOT-compile every bucket program of one plan into the enabled
    persistent cache; mark the bucket set warm.  Returns the summary
    row (shared by the CLI below and tests)."""
    from superlu_dist_tpu.numeric.mega import MegaExecutor
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    from superlu_dist_tpu.utils.jaxcache import mark_bucket_set_warm

    mark = COMPILE_STATS.marker()
    t0 = time.perf_counter()
    ex = MegaExecutor(plan, dtype)
    n = ex.prebake()
    recs = COMPILE_STATS.records[mark:]
    digest = plan.bucket_set_digest()
    mark_bucket_set_warm(digest)
    return {
        "n": plan.n,
        "dtype": str(dtype),
        "bucket_set": list(map(list, plan.bucket_set)),
        "bucket_set_digest": digest,
        "n_kernels": n,
        "seconds": round(time.perf_counter() - t0, 3),
        "trace_seconds": round(sum(r.trace_seconds or 0 for r in recs), 3),
        "lower_seconds": round(sum(r.lower_seconds or 0 for r in recs), 3),
        "compile_seconds": round(sum(r.compile_seconds or 0
                                     for r in recs), 3),
        # time on programs the persistent cache did NOT serve — exactly
        # 0.0 once the bucket set is resident (the warm-start proof)
        "fresh_seconds": round(sum(r.seconds for r in recs
                                   if not r.persistent_hit), 3),
        "persistent_hits": sum(1 for r in recs if r.persistent_hit),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nx", type=int, nargs="+", default=[16, 32, 48])
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--bundle", default=None,
                    help="warm the plan of a persisted LU handle instead")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_platforms", "cpu") \
        if os.environ.get("JAX_PLATFORMS", "") in ("", "cpu") else None
    from superlu_dist_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()

    if args.bundle:
        from superlu_dist_tpu.persist import load_lu
        lu = load_lu(args.bundle)
        plans = [lu.plan]
        if not plans[0].closed:
            print("warm_compile_cache: note — bundle plan is not "
                  "closed (SLU_TPU_BUCKET_CLOSED=0 at factor time); "
                  "prebaking its open key set anyway", file=sys.stderr)
    else:
        plans = [_closed_bench_plan(nx) for nx in args.nx]

    for plan in plans:
        row = warm_plan(plan, args.dtype)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Bucketed mega-kernel executor (numeric/mega.py) + shape-key closure.

The contract under test is ROADMAP item 2 / ISSUE 11: the compiled-
program count must be INDEPENDENT of matrix size (the BENCH_r02 compile
wall: 119 kernels / 455 groups at n=110592, dead in `factor-compile`
before one factor FLOP), while the factors stay BITWISE identical to
the streamed and fused executors — closure and metadata padding are
index-sentinel no-ops, never arithmetic.
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.mega

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _analyzed(a, **symb_kw):
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order, **symb_kw)
    return sf, sym.data[sf.value_perm], a.norm_max()


def _assert_fronts_bitwise(fa, fb):
    assert len(fa.fronts) == len(fb.fronts)
    for (l1, u1), (l2, u2) in zip(fa.fronts, fb.fronts):
        assert np.array_equal(np.asarray(l1), np.asarray(l2))
        assert np.array_equal(np.asarray(u1), np.asarray(u2))
    assert fa.tiny_pivots == fb.tiny_pivots


# ---------------------------------------------------------------------------
# the unified bucket ladder
# ---------------------------------------------------------------------------

def test_ladder_unifies_stream_and_plan_rungs():
    """One recurrence serves both historical ladders: stream._bucket_len
    reproduces the pow-2/pow-4 rounding exactly, and _bucket_sizes
    reproduces its additive-geometric rungs."""
    from superlu_dist_tpu.numeric.plan import _bucket_sizes, bucket_rung
    from superlu_dist_tpu.numeric.stream import _bucket_len

    for n, lo, base, want in [(1, 1, 2.0, 1), (3, 1, 2.0, 4),
                              (8, 8, 2.0, 8), (9, 8, 2.0, 16),
                              (24, 8, 2.0, 32), (3, 1, 4.0, 4),
                              (65, 64, 4.0, 256), (257, 64, 4.0, 1024)]:
        assert _bucket_len(n, lo, base) == want, (n, lo, base)
        assert bucket_rung(n, lo=lo, growth=base) == want
    # the plan's front-bucket rungs (min_bucket=8, growth=1.5) keep
    # their historical values below the tight top rung
    assert list(_bucket_sizes(100, 8, 1.5)) == [8, 16, 24, 40, 64, 96, 104]


def test_bucket_knobs_drive_default_ladder(monkeypatch):
    from superlu_dist_tpu.numeric.plan import bucket_rung

    monkeypatch.setenv("SLU_TPU_BUCKET_BASE", "16")
    monkeypatch.setenv("SLU_TPU_BUCKET_GROWTH", "4.0")
    assert bucket_rung(3) == 16
    assert bucket_rung(17) == 64


# ---------------------------------------------------------------------------
# shape-key closure
# ---------------------------------------------------------------------------

def test_closure_bounds_key_count_and_canonicalizes():
    """A closed plan carries at most max_keys (W, U) keys, every key a
    canonical ladder rung, and the digest is a pure function of the
    set."""
    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric.plan import (bucket_rung, build_plan,
                                               ladder_rungs)

    sf, _, _ = _analyzed(poisson3d(10))
    open_plan = build_plan(sf, closed=False)
    for k in (2, 4, 6):
        plan = build_plan(sf, closed=True, max_keys=k)
        assert plan.closed
        assert 1 <= len(plan.bucket_set) <= k
        for (w, u) in plan.bucket_set:
            assert w == bucket_rung(w), (w, u)
            assert u == 0 or u == bucket_rung(u), (w, u)
        assert plan.bucket_set == tuple(sorted({(g.w, g.u)
                                                for g in plan.groups}))
        plan2 = build_plan(sf, closed=True, max_keys=k)
        assert plan.bucket_set_digest() == plan2.bucket_set_digest()
    assert not open_plan.closed
    assert open_plan.bucket_set_digest() != build_plan(
        sf, closed=True, max_keys=2).bucket_set_digest()


def test_closed_plans_stay_bitwise_across_schedules():
    """Closure runs BEFORE the schedule branch (like alignment), so the
    PR 5 level/dataflow bitwise guarantee carries over to closed
    plans."""
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan

    sf, vals, anorm = _analyzed(poisson2d(16))
    plan_l = build_plan(sf, schedule="level", closed=True)
    plan_d = build_plan(sf, schedule="dataflow", closed=True)
    f_l = numeric_factorize(plan_l, vals, anorm, executor="fused")
    f_d = numeric_factorize(plan_d, vals, anorm, executor="fused")
    widths = np.diff(sf.sn_start)
    us = np.array([len(r) for r in sf.sn_rows])
    for s in range(sf.n_supernodes):
        ga, sa = int(plan_l.sn_group[s]), int(plan_l.sn_slot[s])
        gb, sb = int(plan_d.sn_group[s]), int(plan_d.sn_slot[s])
        wr, ur = int(widths[s]), int(us[s])
        for i, (pa, pb) in enumerate(zip(f_l.fronts[ga], f_d.fronts[gb])):
            assert np.array_equal(np.asarray(pa[sa]), np.asarray(pb[sb])), \
                (s, i)


def test_env_knob_drives_closure(monkeypatch):
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.plan import build_plan

    sf, _, _ = _analyzed(poisson2d(12))
    monkeypatch.setenv("SLU_TPU_BUCKET_CLOSED", "1")
    monkeypatch.setenv("SLU_TPU_BUCKET_KEYS", "2")
    plan = build_plan(sf)
    assert plan.closed and len(plan.bucket_set) <= 2


# ---------------------------------------------------------------------------
# bitwise equivalence: mega == stream == fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case,dtype", [
    ("poisson", "float32"),
    ("poisson", "float64"),
    ("hilbert", "float64"),
    ("hilbert", "complex128"),
    ("arrowhead", "float32"),
])
def test_bitwise_mega_vs_stream_vs_fused(case, dtype):
    """Same closed plan, three executors: the factored L/U panel stacks
    must match BITWISE (np.array_equal, no tolerance).  Coverage
    includes the ill-conditioned (hilbert) and structurally singular
    (rank_deficient_arrowhead, ReplaceTinyPivot path) cases."""
    from superlu_dist_tpu.models.gallery import (
        hilbert, poisson2d, rank_deficient_arrowhead)
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan

    a = {"poisson": lambda: poisson2d(16),
         "hilbert": lambda: hilbert(48),
         "arrowhead": lambda: rank_deficient_arrowhead(40)}[case]()
    sf, vals, anorm = _analyzed(a)
    plan = build_plan(sf, closed=True)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        vals = vals.astype(np.complex128) * (1 + 0.25j)
    f_s = numeric_factorize(plan, vals, anorm, dtype=dtype,
                            executor="stream")
    f_m = numeric_factorize(plan, vals, anorm, dtype=dtype,
                            executor="mega")
    f_f = numeric_factorize(plan, vals, anorm, dtype=dtype,
                            executor="fused")
    _assert_fronts_bitwise(f_s, f_m)
    _assert_fronts_bitwise(f_s, f_f)


def test_df64_on_closed_plan_bitwise_across_schedules():
    """The df64 executor consumes closed plans unchanged: level vs
    dataflow closed plans produce bitwise-identical emulated-double
    factors (the closure pass is schedule-invariant padding, so the
    PR 5 guarantee holds for the error-free-transform path too)."""
    from superlu_dist_tpu.models.gallery import hilbert
    from superlu_dist_tpu.numeric.df64_factor import df64_numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan

    sf, vals, anorm = _analyzed(hilbert(32))
    plan_l = build_plan(sf, schedule="level", closed=True)
    plan_d = build_plan(sf, schedule="dataflow", closed=True)
    f_l = df64_numeric_factorize(plan_l, vals, anorm)
    f_d = df64_numeric_factorize(plan_d, vals, anorm)
    widths = np.diff(sf.sn_start)
    us = np.array([len(r) for r in sf.sn_rows])
    for s in range(sf.n_supernodes):
        ga, sa = int(plan_l.sn_group[s]), int(plan_l.sn_slot[s])
        gb, sb = int(plan_d.sn_group[s]), int(plan_d.sn_slot[s])
        for pa, pb in zip(f_l.fronts[ga], f_d.fronts[gb]):
            assert np.array_equal(np.asarray(pa[sa]), np.asarray(pb[sb]))


# ---------------------------------------------------------------------------
# O(1) compiled-program count
# ---------------------------------------------------------------------------

def test_kernel_count_constant_in_n():
    """The gate invariant (scripts/compile_census.py --buckets): under
    the bench blocking the closed mega program count is the SAME at
    every gallery size, while the streamed per-key count grows."""
    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric.mega import MegaExecutor
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.numeric.stream import StreamExecutor

    counts, stream_counts = [], []
    for nx in (12, 16, 20):
        sf, _, _ = _analyzed(poisson3d(nx), relax=128, max_supernode=256,
                             amalg_tol=1.05)
        plan = build_plan(sf, min_bucket=16, growth=1.05, closed=True)
        counts.append(MegaExecutor(plan, "float32").n_kernels)
        stream_counts.append(StreamExecutor(plan, "float32").n_kernels)
    assert len(set(counts)) == 1, counts
    assert counts[-1] <= stream_counts[-1]
    assert stream_counts[-1] > stream_counts[0] or \
        counts[-1] < stream_counts[-1]


def test_mega_accepts_mesh():
    import jax
    from jax.sharding import Mesh

    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.factor import get_executor
    from superlu_dist_tpu.numeric.mega import MegaExecutor
    from superlu_dist_tpu.numeric.plan import build_plan

    sf, _, _ = _analyzed(poisson2d(10))
    plan = build_plan(sf, closed=True)
    devs = np.array(jax.devices()[:2]).reshape(2, 1)
    mesh = Mesh(devs, ("snode", "panel"))
    # mega composes under a mesh now (GSPMD-sharded bucket programs) —
    # an explicit mega request keeps the MegaExecutor instead of
    # downgrading to stream; tests/test_spmd.py pins the numerics
    ex = MegaExecutor(plan, "float64", mesh=mesh)
    assert ex.mesh is mesh
    ex = get_executor(plan, "float64", executor="mega", mesh=mesh)
    assert isinstance(ex, MegaExecutor) and ex.mesh is mesh
    with pytest.raises(ValueError):
        get_executor(plan, "float64", executor="bogus")


def test_executor_knob_through_driver(monkeypatch):
    """SLU_TPU_EXECUTOR=mega + SLU_TPU_BUCKET_CLOSED=1 steer a full
    gssvx solve through the mega executor and still hit reference
    accuracy."""
    import superlu_dist_tpu as slu
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.mega import MegaExecutor

    monkeypatch.setenv("SLU_TPU_EXECUTOR", "mega")
    monkeypatch.setenv("SLU_TPU_BUCKET_CLOSED", "1")
    a = poisson2d(12)
    xt = np.random.default_rng(3).standard_normal(a.n_rows)
    b = a.matvec(xt)
    x, lu, stats, info = slu.gssvx(slu.Options(), a, b)
    assert info == 0
    assert lu.plan.closed
    assert any(isinstance(fn, MegaExecutor)
               for fn in lu.plan._factor_fns.values())
    assert np.linalg.norm(b - a.matvec(x)) / np.linalg.norm(b) < 1e-12


# ---------------------------------------------------------------------------
# checkpoint -> interrupt -> resume, bitwise, executor-portable
# ---------------------------------------------------------------------------

def test_mega_checkpoint_resume_bitwise_and_portable(tmp_path):
    """A mega run interrupted at a group boundary resumes BITWISE — and
    because frontiers store the UNPADDED pool, the same checkpoint also
    resumes under the streamed executor (deployment can switch
    executors mid-recovery)."""
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.testing.chaos import CountdownDeadline
    from superlu_dist_tpu.utils.errors import DeadlineExceededError

    sf, vals, anorm = _analyzed(poisson2d(20))
    plan = build_plan(sf, closed=True)
    ref = numeric_factorize(plan, vals, anorm, executor="mega")
    assert len(plan.groups) >= 5
    for resume_exec in ("mega", "stream"):
        ck = str(tmp_path / f"ck_{resume_exec}")
        with pytest.raises(DeadlineExceededError):
            numeric_factorize(plan, vals, anorm, executor="mega",
                              ckpt_dir=ck, ckpt_every=1,
                              deadline=CountdownDeadline(3))
        res = numeric_factorize(plan, vals, anorm, executor=resume_exec,
                                resume_from=ck)
        assert res.resumed_groups > 0
        _assert_fronts_bitwise(ref, res)


# ---------------------------------------------------------------------------
# warm start: two-run subprocess pair against one persistent cache
# ---------------------------------------------------------------------------

_WARM_CHILD = """
import sys, json
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
from superlu_dist_tpu.utils.jaxcache import enable_compile_cache
enable_compile_cache(sys.argv[1])
from superlu_dist_tpu.models.gallery import poisson2d
from superlu_dist_tpu.ordering.dispatch import get_perm_c
from superlu_dist_tpu.sparse.formats import symmetrize_pattern
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
from superlu_dist_tpu.numeric.plan import build_plan
from superlu_dist_tpu.numeric.factor import numeric_factorize
from superlu_dist_tpu.utils.options import Options
from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
a = poisson2d(24)
sym = symmetrize_pattern(a)
sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym))
plan = build_plan(sf, closed=True)
numeric_factorize(plan, sym.data[sf.value_perm], a.norm_max(),
                  executor="mega")
blk = COMPILE_STATS.block()
recs = [r for r in COMPILE_STATS.records if r.site == "mega._kernel"]
print(json.dumps({
    "digest": plan.bucket_set_digest(),
    "seconds": blk["seconds"],
    "fresh": blk["fresh_seconds"],
    "xla": sum(r.compile_seconds or 0.0 for r in recs),
    "hits": blk["persistent_hits"],
    "builds": len(recs)}))
"""


def test_warm_start_second_run_compiles_nothing(tmp_path):
    """The acceptance pair (ISSUE 11): two subprocess runs of the SAME
    matrix against one persistent cache.  The second run's FRESH
    compile seconds (time on programs the cache did not serve) must be
    < 5% of the cold run's — it is exactly 0.0 when every program disk-
    hits — and the XLA compile stage must collapse too."""
    child = tmp_path / "warm_child.py"
    child.write_text(_WARM_CHILD)
    cache = str(tmp_path / "jaxcache")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    # the pair must share the test's own cache, not one set outside
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    rows = []
    for _ in range(2):
        r = subprocess.run([sys.executable, str(child), cache], env=env,
                           stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        assert r.returncode == 0, r.stderr.decode()
        rows.append(json.loads(r.stdout.decode().strip().splitlines()[-1]))
    cold, warm = rows
    assert cold["digest"] == warm["digest"]
    assert cold["builds"] == warm["builds"] > 0
    assert cold["hits"] == 0 and warm["hits"] == warm["builds"]
    assert cold["fresh"] > 0
    assert warm["fresh"] < 0.05 * cold["fresh"], (cold, warm)
    assert warm["xla"] < 0.5 * cold["xla"], (cold, warm)


def test_warm_compile_cache_prebake(tmp_path):
    """scripts/warm_compile_cache.py prebakes a closed bucket set with
    ZERO factorization work and marks it warm; a MegaExecutor built
    afterwards in the same process reuses the census-accounted
    programs."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import warm_compile_cache as wcc
    finally:
        sys.path.pop(0)
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.utils import jaxcache

    sf, _, _ = _analyzed(poisson2d(12))
    plan = build_plan(sf, closed=True)
    row = wcc.warm_plan(plan, "float64")
    assert row["n_kernels"] == len(plan.bucket_set)
    assert row["bucket_set_digest"] == plan.bucket_set_digest()
    assert jaxcache.bucket_set_warm(plan.bucket_set_digest())


# ---------------------------------------------------------------------------
# census pending-key accounting (the watchdog postmortem bugfix)
# ---------------------------------------------------------------------------

def test_census_pending_keys_name_uncompiled_buckets():
    """Executors announce their full expected kernel set; build()
    retires keys as they build — the delta is the `pending_kernels`
    list a factor-compile watchdog row emits so the postmortem names
    the offenders (the BENCH_r02 gap)."""
    import time

    from superlu_dist_tpu.obs.compilestats import CompileStats

    cs = CompileStats()
    cs.announce("mega._kernel", ["lu b4 m64 w32 u32", "lu b8 m96 w64 u32"])
    assert {p["key"] for p in cs.pending()} == {"lu b4 m64 w32 u32",
                                                "lu b8 m96 w64 u32"}
    with cs.build("mega._kernel", "lu b4 m64 w32 u32"):
        pass
    assert [p["key"] for p in cs.pending()] == ["lu b8 m96 w64 u32"]
    # a built key is never re-announced (warmed executor, same plan)
    cs.announce("mega._kernel", ["lu b4 m64 w32 u32"])
    assert [p["key"] for p in cs.pending()] == ["lu b8 m96 w64 u32"]
    with cs.build("mega._kernel", "lu b8 m96 w64 u32"):
        pass
    assert cs.pending() == []


def test_executors_announce_their_key_sets():
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.mega import MegaExecutor
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS

    sf, vals, anorm = _analyzed(poisson2d(14))
    plan = build_plan(sf, closed=True)
    ex = MegaExecutor(plan, "float64")
    mine = [p for p in COMPILE_STATS.pending()
            if p["site"] == "mega._kernel"]
    # every one of this executor's not-yet-built buckets is pending
    labels = {ex._census_label(key) for key, _, _, _, _ in ex._steps}
    unbuilt = labels - {r.key for r in COMPILE_STATS.records
                        if r.site == "mega._kernel"}
    assert unbuilt <= {p["key"] for p in mine}
    # factorizing retires them
    import jax.numpy as jnp
    ex(jnp.asarray(vals), jnp.asarray(np.float64(1e-10)))
    after = {p["key"] for p in COMPILE_STATS.pending()
             if p["site"] == "mega._kernel"}
    assert not (labels & after)


# ---------------------------------------------------------------------------
# bench row acceptance fields (subprocess, mega granularity)
# ---------------------------------------------------------------------------

def test_bench_row_carries_mega_census_fields(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_NX="6",
               BENCH_REPS="1",
               BENCH_DEADLINE_S="420", BENCH_GRANULARITY="mega",
               BENCH_SOLVE_NRHS="")
    env.pop("SLU_TPU_TRACE", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, cwd=REPO, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    assert r.returncode == 0, r.stderr.decode()
    row = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert row["value"] is not None
    assert row["granularity"] == "mega"
    assert row["bucket_closed"] is True
    assert row["n_kernels"] == row["n_kernels_compiled"] > 0
    assert isinstance(row["bucket_set_digest"], str)
    assert row["compile_seconds"] >= row.get("xla_compile_seconds", 0) > 0
    assert "compile_fresh_seconds" in row
    # nothing left pending after a completed factor-compile phase
    assert "pending_kernels" not in row

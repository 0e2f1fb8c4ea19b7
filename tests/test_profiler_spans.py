"""The program's spans on the JAX profiler's clock (obs/trace.py's
profiler sink), the build spans of the compile census
(obs/compilestats.py), the escalation ladder's rung spans and the names
of the jitted programs a device trace reports.

Each profiled test runs ``gssvx`` under ``jax.profiler.start_trace`` and
reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``: the
``slu.<cat>.<name>`` host events must be there, on the right lines and
properly nested."""

import collections
import glob
import importlib.util
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import superlu_dist_tpu as slu
from superlu_dist_tpu.models.gallery import hilbert, poisson2d, poisson3d
from superlu_dist_tpu.obs import trace
from superlu_dist_tpu.obs.compilestats import COMPILE_STATS, call

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the benchmark configuration's TPU blocking (benchmark/configs/
#: poisson3d_48.json) at its CPU test size, poisson3d(8)
BLOCKING = dict(relax=256, max_supernode=1024, min_bucket=32,
                bucket_growth=1.3, amalg_tol=1.2, factor_dtype="float32")


@pytest.fixture(autouse=True)
def _no_tracer(monkeypatch):
    """Spans reach the profiler whatever tracer is on: run with none."""
    for knob in ("SLU_TPU_TRACE", "SLU_TPU_FLIGHTREC"):
        monkeypatch.delenv(knob, raising=False)
    trace._reset()
    yield
    trace._reset()


def _profiled(tdir, fn):
    """Run ``fn`` under the JAX profiler; returns (fn's result, the slu.*
    host events as (name, line, start_ns, end_ns, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tdir), "**", "*.xplane.pb"),
                      recursive=True)
    with open(path, "rb") as f:
        pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("slu."):
                    events.append((e.name, (plane.name, li), e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   {k: v for k, v in e.stats}))
    return out, events


def _inside(child, parent, same_line=True):
    return ((not same_line or child[1] == parent[1])
            and parent[2] <= child[2] and child[3] <= parent[3])


def test_gssvx_spans_on_profiler_clock(tmp_path, fresh_solve_programs):
    """DOFACT then a FACTORED device solve, profiled: phase spans, the
    factor's and the sweeps' build spans and the device-solve span are
    host events on the profiler's clock, properly nested.  A device solve
    counts the programs it built as misses, those it reused as hits."""
    a = poisson3d(8)
    b = a.matvec(np.ones(a.n_rows))

    def both():
        x, lu, stats, info = slu.gssvx(slu.Options(**BLOCKING), a, b)
        assert info == 0
        lu.solve_path = "device"          # the accelerator's solve path
        x, lu, stats, info = slu.gssvx(
            slu.Options(fact=slu.Fact.FACTORED, **BLOCKING), a, b, lu=lu)
        assert info == 0 and stats.refine_steps > 0
        return x

    x, events = _profiled(tmp_path, both)
    assert np.allclose(x, 1.0)
    by = collections.defaultdict(list)
    for ev in events:
        by[ev[0]].append(ev)
    for name in ("slu.phase.FACT", "slu.phase.SOLVE", "slu.phase.REFINE",
                 "slu.kernel.device-solve", "slu.compile.solve"):
        assert by[name], (name, sorted(by))
    factor_builds = [ev for ev in events if ev[0] in (
        "slu.compile.stream", "slu.compile.fused", "slu.compile.mega")]
    assert factor_builds, sorted(by)
    # a factor build lies inside the FACT phase (in time: the parallel
    # ahead-of-time builds run on worker threads)
    for ev in factor_builds:
        assert any(_inside(ev, f, same_line=False)
                   for f in by["slu.phase.FACT"]), ev
        assert ev[4]["key"] and "persistent_hit" in ev[4]
    # the sweeps build inside the device solve that first runs them,
    # which runs inside the SOLVE or REFINE phase
    solves = by["slu.kernel.device-solve"]
    for ev in by["slu.compile.solve"]:
        assert any(_inside(ev, s) for s in solves), ev
    keys = {ev[4]["key"].split()[0] for ev in by["slu.compile.solve"]}
    assert {"solve_fwd", "solve_bwd"} <= keys
    phases = by["slu.phase.SOLVE"] + by["slu.phase.REFINE"]
    for ev in solves:
        assert any(_inside(ev, p) for p in phases), ev
        assert ev[4]["nrhs"] == 1
        built = [b for b in by["slu.compile.solve"] + by[
            "slu.compile.diag_inv"] if _inside(b, ev)]
        assert ev[4]["program_misses"] == len(built), ev
        assert ev[4]["program_hits"] + len(built) >= 2, ev
    assert solves[0][4]["program_misses"] > 0
    assert solves[-1][4]["program_misses"] == 0


def test_rung_spans_match_solve_report(tmp_path):
    """hilbert(8) at the bf16 tier climbs the ladder: one slu.rung.<name>
    span per rung the SolveReport records, with its berr before and
    after."""
    a = hilbert(8)
    b = a.matvec(np.ones(a.n_rows))
    (x, lu, stats, info), events = _profiled(tmp_path, lambda: slu.gssvx(
        slu.Options(gemm_prec="bf16", factor_dtype="float32"), a, b))
    assert info == 0
    rungs = [r.name for r in stats.solve_report.rungs]
    assert "gemm-precision" in rungs
    spans = [ev for ev in events if ev[0].startswith("slu.rung.")]
    assert (collections.Counter(ev[0] for ev in spans)
            == collections.Counter("slu.rung." + n for n in rungs))
    gemm = [ev for ev in spans if ev[0] == "slu.rung.gemm-precision"]
    assert len(gemm) == rungs.count("gemm-precision")
    for ev in gemm:
        assert {"tier", "berr_before", "berr_after", "adopted"} <= set(ev[4])
    # the rung's refactor runs inside it
    facts = [ev for ev in events if ev[0] == "slu.phase.FACT"]
    assert any(_inside(f, g) for f in facts for g in gemm)


def test_no_file_without_profiler_or_trace(tmp_path, monkeypatch):
    """No profiler running and no SLU_TPU_TRACE: a solve writes nothing."""
    monkeypatch.chdir(tmp_path)
    a = poisson2d(6)
    x, lu, stats, info = slu.gssvx(slu.Options(), a, np.ones(a.n_rows))
    assert info == 0
    assert trace.get_tracer() is trace.NULL_TRACER
    assert os.listdir(tmp_path) == []


def _small_plan():
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options
    a = poisson3d(6)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym))
    return build_plan(sf), sym.data[sf.value_perm]


def _module_name(lowered) -> str:
    return lowered.as_text().split("module @", 1)[1].split()[0]


def test_factor_program_names_carry_shape_keys():
    """Each streamed factor kernel lowers to a module named after its
    front shape: one name per shape key, none of them ``jit_step``."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor, _kernel
    from superlu_dist_tpu.ops.dense import pivot_kernel
    plan, avals = _small_plan()
    ex = StreamExecutor(plan, "float64")
    avals = jnp.asarray(avals)
    pool = jnp.zeros(plan.pool_size, jnp.float64)
    thresh = jnp.asarray(0.0)
    names = {}
    for key, a, child_arrs, _, _ in ex._steps:
        fn = _kernel(*key, None, False, pivot_kernel(), ex.gemm_prec)
        names[key[0]] = _module_name(
            fn.lower(avals, pool, thresh, *a, *child_arrs))
    assert len(names) > 1
    for (b, m, w, u), name in names.items():
        assert name == f"jit_factor_b{b}_m{m}_w{w}_u{u}"
    assert len(set(names.values())) == len(names)


@pytest.mark.parametrize("trans", [False, True])
def test_solve_program_names(trans):
    """The fused sweeps lower to ``solve_fwd`` / ``solve_bwd`` (and the
    transpose pair ``solve_fwd_trans`` / ``solve_bwd_trans``)."""
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.solve.device import DeviceSolver
    plan, avals = _small_plan()
    fact = numeric_factorize(plan, avals, float(np.abs(avals).max()))
    solver = DeviceSolver(fact)
    assert solver.fused
    x = jnp.zeros((plan.n + 1, 1), jnp.float64)
    idx = [(f, r, w) for _, f, r, w in solver._groups]
    if trans:
        fwd, bwd = solver._fused_trans_fns(False)
        names = (_module_name(fwd.lower(x, x, solver.fronts, idx)),
                 _module_name(bwd.lower(x, solver.fronts, idx)))
        assert names == ("jit_solve_fwd_trans", "jit_solve_bwd_trans")
    else:
        fwd, bwd = solver._fused_fns()
        invs = solver._invs
        names = (_module_name(fwd.lower(x, x, solver.fronts, idx, invs)),
                 _module_name(bwd.lower(x, solver.fronts, idx, invs)))
        assert names == ("jit_solve_fwd", "jit_solve_bwd")


def test_device_solve_builds_are_censused_per_sweep(fresh_solve_programs):
    """A new solver's first solve records one build per sweep program
    (``solve`` site, keyed by program and nrhs bucket); its next solve
    builds nothing."""
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.solve.device import DeviceSolver
    plan, avals = _small_plan()
    fact = numeric_factorize(plan, avals, float(np.abs(avals).max()))
    solver = DeviceSolver(fact)
    m0 = COMPILE_STATS.marker()
    solver.solve(np.ones(plan.n))
    recs = COMPILE_STATS.records[m0:]
    assert sorted(r.key.split()[0] for r in recs) == ["solve_bwd",
                                                      "solve_fwd"]
    assert all(r.site == "solve" and r.seconds > 0 for r in recs)
    m1 = COMPILE_STATS.marker()
    solver.solve(np.ones(plan.n))
    assert COMPILE_STATS.marker() == m1


def test_refinement_spmv_build_is_censused(fresh_solve_programs):
    """A DeviceSpMV's first product is a ``spmv`` build; later products
    with the same signature are not."""
    from superlu_dist_tpu.parallel.dist import DeviceSpMV
    a = poisson2d(5)
    op = DeviceSpMV(a)
    m0 = COMPILE_STATS.marker()
    y = op.matvec(np.ones(a.n_rows))
    assert np.allclose(y, a.matvec(np.ones(a.n_rows)))
    recs = COMPILE_STATS.records[m0:]
    assert [r.site for r in recs] == ["spmv"]
    assert recs[0].key.startswith("refine_spmv ")
    op.matvec(np.ones(a.n_rows))
    assert COMPILE_STATS.marker() == m0 + 1


def test_refinement_spmv_outlives_its_matrix(fresh_solve_programs):
    """Two operators on one pattern with different values build
    ``refine_spmv`` once; each product is its own matrix's."""
    from superlu_dist_tpu.parallel.dist import DeviceSpMV
    a = poisson2d(5)
    a2 = type(a)(a.n_rows, a.n_cols, a.indptr, a.indices,
                 a.data * np.linspace(1.0, 2.0, a.nnz))
    x = np.random.default_rng(2).standard_normal(a.n_rows)
    m0 = COMPILE_STATS.marker()
    for m in (a, a2):
        op = DeviceSpMV(m)
        np.testing.assert_allclose(op.matvec(x), m.matvec(x), rtol=1e-13,
                                   atol=1e-13)
    recs = COMPILE_STATS.records[m0:]
    assert [r.key.split()[0] for r in recs] == ["refine_spmv"]


@pytest.mark.parametrize("event,hit", [
    ("/jax/compilation_cache/cache_hits", True),
    ("/jax/compilation_cache/cache_misses", False),
    (None, None)])
def test_persistent_hit_from_jax_events(event, hit):
    """A build's persistent_hit is what JAX reported on the building
    thread: a cache hit, a miss (compiled and written), or neither."""
    from superlu_dist_tpu.obs.compilestats import CompileStats
    cs = CompileStats()

    def elsewhere():
        # another thread's lookups are not this build's
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")

    with cs.build("test.site", "k", n_args=1):
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
        if event is not None:
            jax.monitoring.record_event(event)
    rec, = cs.records
    assert rec.persistent_hit is hit
    assert rec.site == "test.site" and rec.seconds >= 0


def test_first_call_builds_once_per_signature():
    """``compilestats.call``: the first call per argument signature is a
    build; a new signature is another.  ``tally`` counts this thread's
    calls in its body as misses (builds) and hits."""
    from superlu_dist_tpu.obs.compilestats import tally
    fn = jax.jit(lambda v: v * 2)
    m0 = COMPILE_STATS.marker()
    with tally() as programs:
        call("test.call", "double", fn, jnp.ones(3))
        call("test.call", "double", fn, jnp.ones(3))
        assert COMPILE_STATS.marker() == m0 + 1
        t = threading.Thread(target=call,
                             args=("test.call", "double", fn, jnp.ones(3)))
        t.start()
        t.join()
    assert (programs.hits, programs.misses) == (1, 1)
    call("test.call", "double", fn, jnp.ones(4))
    assert COMPILE_STATS.marker() == m0 + 2
    assert (programs.hits, programs.misses) == (1, 1)


def _span_report():
    spec = importlib.util.spec_from_file_location(
        "span_report", os.path.join(REPO, "scripts", "span_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_span_report_rows_count_self_and_idle():
    """scripts/span_report.py's rows on synthetic host lines (ns):
    durations clipped to the window, self time less the slu.* spans
    nested on the same line, and the first chip's idle time inside each
    name's intervals."""
    sr = _span_report()
    ns = 1e-9
    lines = [[("slu.phase.FACT", 0, 100), ("slu.compile.solve", 10, 30),
              ("slu.kernel.device-solve", 35, 60),
              ("slu.compile.solve", 40, 50)],
             [("slu.phase.FACT", 90, 120), ("slu.rung.gemm-precision",
                                            200, 300)]]
    idle = [(0, 12), (20, 45), (90, 100)]
    spans = sr.span_rows(lines, 5, 95, idle)
    assert set(spans) == {"slu.phase.FACT", "slu.compile.solve",
                          "slu.kernel.device-solve"}
    fact, build, solve = (spans[n] for n in (
        "slu.phase.FACT", "slu.compile.solve", "slu.kernel.device-solve"))
    assert fact["count"] == 2 and build["count"] == 2
    assert fact["seconds"] == pytest.approx(95 * ns)
    assert fact["self_seconds"] == pytest.approx((90 - 20 - 25 + 5) * ns)
    assert build["seconds"] == build["self_seconds"] == pytest.approx(
        30 * ns)
    assert solve["seconds"] == pytest.approx(25 * ns)
    assert solve["self_seconds"] == pytest.approx(15 * ns)
    assert build["idle_seconds"] == pytest.approx((2 + 10 + 5) * ns)
    assert solve["idle_seconds"] == pytest.approx(10 * ns)
    assert fact["idle_seconds"] == pytest.approx((7 + 25 + 5) * ns)
    # no device plane: no idle time
    assert sr.span_rows(lines, 5, 95, None)["slu.phase.FACT"][
        "idle_seconds"] is None


def test_span_report_idle_by_innermost_span_and_gaps():
    """The chip's idle time goes to the shortest slu.* span covering it;
    a long gap is named by a span below the phase level covering 90 % of
    it, or by none."""
    sr = _span_report()
    ns = 1e-9
    host = [("window", 0, 100), ("slu.phase.SOLVE", 0, 100),
            ("slu.kernel.device-solve", 10, 60),
            ("slu.compile.solve", 20, 50), ("slu.compile.spmv", 120, 130)]
    ops = [("fusion", 0, 10), ("fusion", 55, 70), ("fusion", 90, 95)]
    planes = [("/host:CPU", [("python", host)]),
              ("/device:TPU:0", [("XLA Modules", []), ("XLA Ops", ops)])]
    r = sr.report(planes, gap_s=20 * ns)
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["idle_s"] == pytest.approx(70 * ns)
    assert r["idle_by_span"] == pytest.approx({
        "slu.compile.solve": 30 * ns, "slu.phase.SOLVE": 25 * ns,
        "slu.kernel.device-solve": 15 * ns})
    assert [g["span"] for g in r["gaps"]] == [
        "slu.kernel.device-solve", None]
    assert r["gaps"][0]["seconds"] == pytest.approx(45 * ns)
    assert "slu.compile.spmv" not in r["spans"]
    assert r["spans"]["slu.kernel.device-solve"][
        "idle_seconds"] == pytest.approx(45 * ns)
    assert r["events"] == {"/host:CPU": 5, "/device:TPU:0": 3}


def test_span_report_of_profiled_gssvx(tmp_path, capsys):
    """A profiled gssvx call, read back by scripts/span_report.py: one row
    per slu.* span name with the trace's count; the CPU trace has no
    device plane, so no idle time."""
    sr = _span_report()
    a = poisson2d(6)

    def window():
        with jax.profiler.TraceAnnotation("window"):
            return slu.gssvx(slu.Options(), a, np.ones(a.n_rows))

    (x, lu, stats, info), events = _profiled(tmp_path, window)
    assert info == 0
    assert sr.main([str(tmp_path)]) == 0
    r = json.loads(capsys.readouterr().out)
    assert ({n: row["count"] for n, row in r["spans"].items()}
            == dict(collections.Counter(ev[0] for ev in events)))
    assert "slu.phase.FACT" in r["spans"]
    for row in r["spans"].values():
        assert 0 <= row["self_seconds"] <= row["seconds"] + 1e-12
        assert row["idle_seconds"] is None
    assert r["idle_s"] is None and r["gaps"] is None

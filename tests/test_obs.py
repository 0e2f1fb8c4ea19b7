"""Observability subsystem (obs/trace.py + compile census + flight
recorder + metrics + comm/kernel telemetry + cross-rank stat
reduction) — the PROFlevel analog.

Covers: span nesting/ordering and both artifact formats (Chrome
trace-event JSON with wall-clock anchor, JSONL sidecar), the
guaranteed-negligible disabled paths (no file / no ring / no registry,
reused no-op singletons), comm counters against a 2-rank TreeComm
exchange with known byte counts, kernel-shape records from both
factorization executors and the device solve, Stats.timer reentrancy,
Stats.reduce min/max/avg + load-balance factors, the compile census
(cold builds recorded with bucket keys + compile trace spans, warm
reruns silent, stats.compile block), flight-recorder postmortems
(bounded ring, dump on provoked NumericBreakdownError and 2-rank
CollectiveMismatchError, tracer composition), the metrics registry
(exports, TreeComm wiring, 2-rank collective reduction, recovery-rung
counters), the bench row's compile/phase acceptance fields, and the
perf-regression gate's seeding/enforcement state machine.
"""

import json
import multiprocessing as mp
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from superlu_dist_tpu import native
from superlu_dist_tpu.obs import trace
from superlu_dist_tpu.utils.stats import (
    COMM_OPS, CommStats, PHASES, Stats, StatsSummary)

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_hygiene(monkeypatch):
    """Every test starts and ends with the env-driven telemetry state
    reset (tracer, flight recorder, and metrics are all latched on
    first use)."""
    from superlu_dist_tpu.obs import flightrec, metrics
    for knob in ("SLU_TPU_TRACE", "SLU_TPU_FLIGHTREC", "SLU_TPU_METRICS"):
        monkeypatch.delenv(knob, raising=False)
    trace._reset()
    flightrec._reset()
    metrics._reset()
    yield
    trace._reset()
    flightrec._reset()
    metrics._reset()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_jsonl(tmp_path):
    t = trace.Tracer(str(tmp_path / "t.json"))
    with t.span("outer", cat="phase", who="test"):
        time.sleep(0.002)
        with t.span("inner", cat="kernel", m=8, w=4):
            time.sleep(0.002)
        with t.span("inner2", cat="comm", bytes=64):
            pass
    t.close()
    rows = [json.loads(line) for line in open(tmp_path / "t.jsonl")]
    # the first record is the wall-clock anchor written at tracer open
    assert [r["name"] for r in rows] == ["clock-anchor", "inner", "inner2",
                                         "outer"]
    assert rows[0]["args"]["unix_time"] > 0
    by = {r["name"]: r for r in rows}
    outer, inner = by["outer"], by["inner"]
    # nesting: children start after and end before the parent
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert by["inner2"]["ts"] >= inner["ts"] + inner["dur"]
    # depth reflects nesting at record time
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert inner["args"] == {"m": 8, "w": 4}
    assert outer["args"] == {"who": "test"}


def test_chrome_trace_artifact_valid(tmp_path):
    path = str(tmp_path / "t.json")
    t = trace.Tracer(path)
    with t.span("a", cat="phase"):
        with t.span("b", cat="kernel"):
            pass
    t.complete("c", "comm", time.perf_counter() - 0.5, 0.01, bytes=3)
    t.close()
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert len(events) == 4          # 3 spans + the wall-clock anchor
    for ev in events:
        assert ev["ph"] == "X"
        for key in ("name", "cat", "ts", "dur", "pid", "tid"):
            assert key in ev
        assert ev["cat"] in trace.CATEGORIES
    # events are sorted: ts monotone per (pid, tid)
    last = {}
    for ev in events:
        key = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(key, float("-inf"))
        last[key] = ev["ts"]


def test_span_set_attaches_midspan_attrs(tmp_path):
    t = trace.Tracer(str(tmp_path / "t.json"))
    with t.span("s", cat="dispatch") as sp:
        sp.set(result_bytes=128)
    t.close()
    rows = [json.loads(line) for line in open(tmp_path / "t.jsonl")]
    assert rows[1]["args"] == {"result_bytes": 128}   # rows[0] = anchor


def test_disabled_path_is_noop(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    t = trace.get_tracer()
    assert t is trace.NULL_TRACER
    assert not t.enabled
    # spans reach the profiler sink only: no file or ring behind them
    assert t.span("b", cat="kernel", x=1)._sink is trace.NULL_SPAN
    with t.span("a") as sp:
        sp.set(ignored=True)
    t.complete("x", "comm", 0.0, 1.0)
    t.flush()
    t.close()
    assert os.listdir(tmp_path) == []        # nothing written, ever
    # near-zero overhead: a hundred thousand disabled spans in well under
    # a second (one TraceMe each, which records nothing with no profiler
    # running, and no clock read)
    t0 = time.perf_counter()
    for _ in range(100_000):
        with t.span("hot", cat="kernel"):
            pass
    assert time.perf_counter() - t0 < 1.0


def test_env_gated_tracer(tmp_path, monkeypatch):
    path = str(tmp_path / "run.json")
    monkeypatch.setenv("SLU_TPU_TRACE", path)
    trace._reset()
    t = trace.get_tracer()
    assert isinstance(t, trace.Tracer) and t.enabled
    with trace.span("gated", cat="phase"):
        pass
    trace._reset()                            # closes + flushes
    doc = json.load(open(path))
    names = [e["name"] for e in doc["traceEvents"]]
    assert names == ["clock-anchor", "gated"]
    assert (tmp_path / "run.jsonl").exists()


def test_install_programmatic(tmp_path):
    t = trace.Tracer(str(tmp_path / "p.json"))
    prev = trace.install(t)
    try:
        assert trace.enabled()
        with trace.span("prog", cat="phase"):
            pass
    finally:
        trace.install(prev)
        t.close()
    rows = [json.loads(line) for line in open(tmp_path / "p.jsonl")]
    assert [r["name"] for r in rows] == ["clock-anchor", "prog"]


# ---------------------------------------------------------------------------
# kernel-shape telemetry (both executors + device solve)
# ---------------------------------------------------------------------------

def _small_plan():
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize

    a = poisson2d(6)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, np.arange(a.n_rows), relax=4,
                            max_supernode=16)
    plan = build_plan(sf)
    return plan, sym.data[sf.value_perm]


def test_stream_executor_kernel_spans(tmp_path):
    import jax.numpy as jnp
    from superlu_dist_tpu.numeric.stream import StreamExecutor

    plan, avals = _small_plan()
    t = trace.Tracer(str(tmp_path / "s.json"))
    prev = trace.install(t)
    try:
        ex = StreamExecutor(plan, "float64")
        ex(jnp.asarray(avals), jnp.asarray(0.0))
    finally:
        trace.install(prev)
        t.close()
    events = json.load(open(tmp_path / "s.json"))["traceEvents"]
    kernels = [e for e in events if e["cat"] == "kernel"]
    dispatch = [e for e in events if e["cat"] == "dispatch"]
    assert len(kernels) == len(plan.groups)
    assert len(dispatch) == len(plan.groups)
    for k in kernels:
        args = k["args"]
        for key in ("level", "batch", "padded_batch", "m", "w", "u",
                    "executed_flops", "structural_flops", "padding"):
            assert key in args, (key, args)
        assert args["executed_flops"] >= args["structural_flops"] > 0
        assert args["padding"] >= 1.0


def test_fused_executor_kernel_span(tmp_path):
    import jax.numpy as jnp
    from superlu_dist_tpu.numeric.factor import make_factor_fn

    plan, avals = _small_plan()
    fn = make_factor_fn(plan, "float64")
    t = trace.Tracer(str(tmp_path / "f.json"))
    prev = trace.install(t)
    try:
        fn(jnp.asarray(avals), jnp.asarray(0.0))
    finally:
        trace.install(prev)
        t.close()
    events = json.load(open(tmp_path / "f.json"))["traceEvents"]
    kernels = [e for e in events if e["cat"] == "kernel"]
    assert len(kernels) == 1 and kernels[0]["name"] == "factor-fused"
    args = kernels[0]["args"]
    assert args["aggregate"] and args["structural_flops"] == plan.flops
    assert any(e["cat"] == "dispatch" for e in events)


def test_device_solve_spans(tmp_path):
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.solve.device import DeviceSolver
    from superlu_dist_tpu.utils.options import IterRefine, Options

    a = poisson2d(7)
    b = np.ones(a.n_rows)
    x, lu, stats, info = gssvx(Options(iter_refine=IterRefine.NOREFINE),
                               a, b)
    assert info == 0
    t = trace.Tracer(str(tmp_path / "d.json"))
    prev = trace.install(t)
    try:
        DeviceSolver(lu.numeric).solve(np.ones(a.n_rows))
    finally:
        trace.install(prev)
        t.close()
    events = json.load(open(tmp_path / "d.json"))["traceEvents"]
    solve = [e for e in events if e["name"] == "device-solve"]
    assert len(solve) == 1 and solve[0]["cat"] == "kernel"
    assert solve[0]["args"]["nrhs"] == 1
    d2h = [e for e in events if e["name"] == "solve-d2h"]
    assert len(d2h) == 1 and d2h[0]["cat"] == "comm"
    assert d2h[0]["args"]["bytes"] > 0


def test_gssvx_emits_phase_spans(tmp_path):
    import superlu_dist_tpu as slu
    from superlu_dist_tpu.models.gallery import poisson2d

    t = trace.Tracer(str(tmp_path / "g.json"))
    prev = trace.install(t)
    try:
        a = poisson2d(6)
        x, lu, stats, info = slu.gssvx(slu.Options(), a,
                                       np.ones(a.n_rows))
        assert info == 0
    finally:
        trace.install(prev)
        t.close()
    events = json.load(open(tmp_path / "g.json"))["traceEvents"]
    phases = {e["name"] for e in events if e["cat"] == "phase"}
    assert {"EQUIL", "ROWPERM", "COLPERM", "SYMBFACT", "DIST", "FACT",
            "SOLVE"} <= phases


# ---------------------------------------------------------------------------
# Stats.timer reentrancy (satellite regression)
# ---------------------------------------------------------------------------

def test_stats_timer_reentrant_same_phase():
    """Nested enters of the SAME phase must not double-count: the outer
    enter owns the accumulation (the old implementation added the inner
    elapsed a second time)."""
    s = Stats()
    with s.timer("FACT"):
        time.sleep(0.05)
        with s.timer("FACT"):
            time.sleep(0.05)
    assert 0.09 <= s.utime["FACT"] < 0.14, s.utime["FACT"]
    assert s._timer_depth["FACT"] == 0


def test_stats_timer_sequential_accumulates():
    s = Stats()
    for _ in range(2):
        with s.timer("SOLVE"):
            time.sleep(0.02)
    assert s.utime["SOLVE"] >= 0.04


def test_stats_timer_reentrant_under_exception():
    s = Stats()
    with pytest.raises(RuntimeError):
        with s.timer("FACT"):
            with s.timer("FACT"):
                raise RuntimeError("boom")
    assert s._timer_depth["FACT"] == 0
    with s.timer("FACT"):        # still usable afterwards
        pass
    assert s.utime["FACT"] > 0


# ---------------------------------------------------------------------------
# cross-rank stat reduction
# ---------------------------------------------------------------------------

class _FakeComm:
    """Two-rank comm stub: rank 0's matrix summed with a preloaded rank-1
    row — exercises the reduce math without the native transport."""

    n_ranks = 2
    rank = 0

    def __init__(self, peer_stats: Stats):
        self._peer_vec = peer_stats._pack()

    def allreduce_sum_any(self, arr, root=0):
        out = np.array(arr, dtype=np.float64)
        out[1] += self._peer_vec
        return out


def test_stats_reduce_min_max_avg_balance():
    s0, s1 = Stats(), Stats()
    s0.utime["FACT"], s1.utime["FACT"] = 1.0, 3.0
    s0.ops["FACT"] = s1.ops["FACT"] = 50.0
    s0.tiny_pivots, s1.tiny_pivots = 2, 3
    s1.comm = {"bcast": {"calls": 4, "bytes": 256, "seconds": 0.5}}
    summary = s0.reduce(_FakeComm(s1))
    assert isinstance(summary, StatsSummary)
    f = summary.utime["FACT"]
    assert f.min == 1.0 and f.max == 3.0 and f.avg == 2.0
    assert abs(f.balance - 1.5) < 1e-12
    assert abs(summary.balance("FACT") - 1.5) < 1e-12
    assert summary.ops["FACT"].total == 100.0
    assert summary.tiny_pivots == 5
    assert summary.comm["bcast"]["calls"] == 4
    assert summary.comm["bcast"]["bytes"] == 256
    rep = summary.report()
    assert "FACT" in rep and "balance" in rep.splitlines()[2]
    # untouched phases don't clutter the report
    assert "EQUIL" not in rep


def test_comm_stats_accounting_and_report():
    cs = CommStats()
    cs.add("bcast", 64, 0.01)
    cs.add("bcast", 64, 0.01)
    cs.add("allreduce", 128, 0.02)
    t = cs.totals()
    assert t["bcast"] == {"calls": 2, "bytes": 128, "seconds": 0.02}
    assert "reduce" not in t                  # zero ops stay out
    assert "bcast" in cs.report()
    s = Stats()
    s.attach_comm(cs)
    assert "comm bcast" in s.report()


# ---------------------------------------------------------------------------
# 2-rank native transport: comm counters with known byte counts + reduce
# ---------------------------------------------------------------------------

def _exchange(tc):
    """The scripted 2-rank exchange: 1 bcast, 1 reduce, 1 allreduce of
    8 float64 each (single chunk at max_len=64)."""
    from superlu_dist_tpu.utils.stats import Stats

    buf = np.arange(8.0) if tc.rank == 0 else np.zeros(8)
    tc.bcast(buf, root=0)
    ok = bool(np.array_equal(buf, np.arange(8.0)))
    buf2 = np.full(8, float(tc.rank + 1))
    tc.reduce_sum(buf2, root=0)
    buf3 = np.ones(8)
    tc.allreduce_sum(buf3, root=0)
    totals = tc.comm_stats.totals()
    st = Stats()
    st.utime["FACT"] = float(tc.rank + 1)
    st.ops["FACT"] = 100.0
    st.tiny_pivots = tc.rank
    st.attach_comm(tc.comm_stats)
    summary = st.reduce(tc)
    return ok, totals, summary


def _obs_rank_worker(name, n_ranks, rank, q):
    from superlu_dist_tpu.parallel.treecomm import TreeComm
    tc = TreeComm(name, n_ranks, rank, max_len=64, create=False)
    try:
        q.put((rank,) + _exchange(tc))
    finally:
        tc.close()


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_comm_counters_and_reduce_two_ranks():
    from superlu_dist_tpu.parallel.treecomm import TreeComm

    name = f"/slu_obs_comm_{os.getpid()}"
    owner = TreeComm(name, 2, 0, max_len=64, create=True)
    try:
        ctx = mp.get_context("spawn")     # no fork of the jax-laden parent
        q = ctx.Queue()
        p = ctx.Process(target=_obs_rank_worker, args=(name, 2, 1, q))
        p.start()
        ok0, totals0, summary0 = _exchange(owner)
        rank1, ok1, totals1, summary1 = q.get(timeout=120)
        p.join(timeout=120)
        assert p.exitcode == 0
    finally:
        owner.close(unlink=True)
    assert ok0 and ok1
    for totals in (totals0, totals1):
        # known byte counts: 8 float64 = 64 bytes per leg
        assert totals["bcast"] == {"calls": 1, "bytes": 64,
                                   "seconds": totals["bcast"]["seconds"]}
        assert totals["reduce"]["calls"] == 1
        assert totals["reduce"]["bytes"] == 64
        # the composite attributes BOTH its legs to "allreduce"
        assert totals["allreduce"]["calls"] == 2
        assert totals["allreduce"]["bytes"] == 128
    # every rank computed the SAME cross-rank summary
    for summary in (summary0, summary1):
        f = summary.utime["FACT"]
        assert f.min == 1.0 and f.max == 2.0 and f.avg == 1.5
        assert abs(f.balance - 2.0 / 1.5) < 1e-12
        assert summary.tiny_pivots == 1
        assert summary.ops["FACT"].total == 200.0
        # comm totals summed over ranks
        assert summary.comm["bcast"]["bytes"] == 128
        assert summary.comm["allreduce"]["bytes"] == 256


# ---------------------------------------------------------------------------
# comm spans from the tree collectives
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_single_rank_comm_spans(tmp_path):
    from superlu_dist_tpu.parallel.treecomm import TreeComm

    t = trace.Tracer(str(tmp_path / "c.json"))
    prev = trace.install(t)
    try:
        name = f"/slu_obs_span_{os.getpid()}"
        with TreeComm(name, 1, 0, max_len=16, create=True) as tc:
            tc.bcast(np.ones(4))
            tc.allreduce_sum(np.ones(4))
            tc.bcast_bytes(b"hello")
    finally:
        trace.install(prev)
        t.close()
    events = json.load(open(tmp_path / "c.json"))["traceEvents"]
    comm = [e for e in events if e["cat"] == "comm"]
    ops = {e["args"]["op"] for e in comm}
    assert {"bcast", "allreduce", "bcast_bytes"} <= ops
    for e in comm:
        assert e["args"]["bytes"] > 0
        assert e["name"].startswith("tree-")


# ---------------------------------------------------------------------------
# mfu_report: structured-trace parsing + explicit empty-input diagnostic
# ---------------------------------------------------------------------------

def _run_mfu(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "mfu_report.py"),
         *args],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_mfu_report_missing_inputs_diagnostic(tmp_path):
    r = _run_mfu(str(tmp_path / "no.jsonl"), str(tmp_path / "no.err"))
    assert r.returncode == 1
    assert b"no trace rows found" in r.stderr


def test_mfu_report_prefers_structured_trace(tmp_path):
    t = trace.Tracer(str(tmp_path / "k.json"))
    t.complete("lu b4 m32 w16 u16", "kernel", 0.0, 0.005, level=2,
               batch=3, padded_batch=4, m=32, w=16, u=16,
               executed_flops=4.0e7, structural_flops=3.0e7, padding=1.33)
    t.close()
    for artifact in ("k.json", "k.jsonl"):
        r = _run_mfu(str(tmp_path / "no.jsonl"), str(tmp_path / artifact))
        assert r.returncode == 0, r.stderr
        out = r.stdout.decode()
        assert "structured trace" in out
        assert "m=32" in out and "lvl=2" in out


def test_mfu_report_legacy_stderr_still_parses(tmp_path):
    err = tmp_path / "legacy.err"
    err.write_text("# lvl=3  B=16  m=512  w=256  u=256  12.34 ms  "
                   "567.8 GF/s\n")
    r = _run_mfu(str(tmp_path / "no.jsonl"), str(err))
    assert r.returncode == 0, r.stderr
    out = r.stdout.decode()
    assert "legacy stderr" in out and "m=512" in out


# ---------------------------------------------------------------------------
# compile census (obs/compilestats.py): cold builds recorded, warm silent
# ---------------------------------------------------------------------------

def test_compile_census_cold_then_warm_stream(tmp_path):
    import jax.numpy as jnp
    from superlu_dist_tpu.numeric import stream as stream_mod
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS

    plan, avals = _small_plan()
    stream_mod._CENSUSED_KEYS.clear()
    m0 = COMPILE_STATS.marker()
    t = trace.Tracer(str(tmp_path / "c.json"))
    prev = trace.install(t)
    try:
        ex = stream_mod.StreamExecutor(plan, "float64")
        ex(jnp.asarray(avals), jnp.asarray(0.0))
        cold = COMPILE_STATS.marker() - m0
        assert cold > 0
        # warm rerun: every key censused, nothing new recorded
        ex(jnp.asarray(avals), jnp.asarray(0.0))
        assert COMPILE_STATS.marker() - m0 == cold
    finally:
        trace.install(prev)
        t.close()
    # record content: site, bucket key, seconds, param count
    recs = COMPILE_STATS.records[m0:]
    assert all(r.site == "stream._kernel" for r in recs)
    assert all(r.key.startswith("lu b") for r in recs)
    assert all(r.seconds >= 0 and r.n_args >= 8 for r in recs)
    # census aggregation ranks buckets by total seconds
    census = COMPILE_STATS.census(m0)
    assert census == sorted(census, key=lambda row: -row["seconds"])
    # the builds landed in the trace as compile-category spans
    events = json.load(open(tmp_path / "c.json"))["traceEvents"]
    spans = [e for e in events if e["cat"] == "compile"]
    assert len(spans) == cold
    for e in spans:
        assert e["name"] == "stream"
        assert e["args"]["site"] == "stream._kernel"
        assert "key" in e["args"]


def test_compile_census_fused_and_stats_block():
    import jax.numpy as jnp
    from superlu_dist_tpu.numeric.factor import make_factor_fn
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS

    plan, avals = _small_plan()
    fn = make_factor_fn(plan, "float64")
    m0 = COMPILE_STATS.marker()
    fn(jnp.asarray(avals), jnp.asarray(0.0))
    assert COMPILE_STATS.marker() - m0 == 1       # one fused program
    fn(jnp.asarray(avals), jnp.asarray(0.0))
    assert COMPILE_STATS.marker() - m0 == 1       # warm: silent
    rec = COMPILE_STATS.records[m0]
    assert rec.site == "make_factor_fn" and rec.key.startswith("fused g")
    blk = COMPILE_STATS.block(since=m0)
    assert blk["builds"] == 1 and blk["seconds"] > 0
    assert blk["census"][0]["site"] == "make_factor_fn"


def test_gssvx_fills_stats_compile_block():
    import superlu_dist_tpu as slu
    from superlu_dist_tpu.models.gallery import poisson2d

    a = poisson2d(9)   # distinct size: guarantees at least one cold build
    x, lu, stats, info = slu.gssvx(slu.Options(), a, np.ones(a.n_rows))
    assert info == 0
    assert isinstance(stats.compile, dict)
    assert {"builds", "seconds", "persistent_hits", "census"} \
        <= set(stats.compile)
    if stats.compile["builds"]:
        assert "compile" in stats.report()


# ---------------------------------------------------------------------------
# flight recorder (obs/flightrec.py)
# ---------------------------------------------------------------------------

def test_flightrec_ring_bounds_and_dump(tmp_path):
    from superlu_dist_tpu.obs import flightrec

    fr = flightrec.FlightRecorder(str(tmp_path / "fr.json"), depth=16)
    with fr.span("FACT", cat="phase"):
        for i in range(40):
            fr.complete(f"ev{i}", "dispatch", time.perf_counter(), 0.0,
                        i=i)
    path = fr.dump("unit-test", detail="ring bounds")
    assert path == str(tmp_path / "fr.json")
    doc = json.load(open(path))
    assert doc["reason"] == "unit-test"
    assert len(doc["events"]) == 16               # bounded, newest kept
    assert doc["total_events"] == 41 and doc["dropped_events"] == 25
    assert doc["events"][-1]["name"] == "FACT"    # span closed last
    assert doc["anchor"]["unix_time"] > 0
    assert "compile" in doc
    # a second dump supersedes (seq advances)
    fr.dump("again")
    assert json.load(open(path))["seq"] == 1


def test_flightrec_is_the_tracer_when_alone(tmp_path, monkeypatch):
    """Flight-only mode: get_tracer() returns the recorder (every
    instrumentation site feeds the ring) but profiling stays OFF — the
    executors must not serialize their dispatch for it."""
    from superlu_dist_tpu.obs import flightrec

    monkeypatch.setenv("SLU_TPU_FLIGHTREC", str(tmp_path / "f-%p.json"))
    flightrec._reset()
    trace._reset()
    t = trace.get_tracer()
    assert isinstance(t, flightrec.FlightRecorder)
    assert t.enabled and not t.profiling and t.path is None
    # both on: a tee that profiles (file tracer wins) and keeps the path
    monkeypatch.setenv("SLU_TPU_TRACE", str(tmp_path / "t.json"))
    flightrec._reset()
    trace._reset()
    t2 = trace.get_tracer()
    assert isinstance(t2, trace.TeeTracer)
    assert t2.profiling and t2.path == str(tmp_path / "t.json")
    with t2.span("both", cat="phase"):
        pass
    trace._reset()
    events = json.load(open(tmp_path / "t.json"))["traceEvents"]
    assert any(e["name"] == "both" for e in events)


def test_flightrec_dump_on_numeric_breakdown(tmp_path):
    """Acceptance: a run killed by an injected breakdown leaves a
    postmortem artifact with the last events and the open phase stack."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.obs import flightrec
    from superlu_dist_tpu.utils.errors import NumericBreakdownError
    from superlu_dist_tpu.utils.options import Options, RowPerm

    fr = flightrec.FlightRecorder(str(tmp_path / "post.json"), depth=128)
    prev = flightrec.install(fr)
    trace._reset()            # recompose: the recorder becomes the tracer
    try:
        a = poisson2d(8)
        a.data = a.data.copy()
        a.data[len(a.data) // 2] = np.nan
        with pytest.raises(NumericBreakdownError) as exc:
            gssvx(Options(equil=False, row_perm=RowPerm.NOROWPERM), a,
                  np.ones(a.n_rows))
    finally:
        flightrec.install(prev)
        trace._reset()
    assert exc.value.flightrec_dump == str(tmp_path / "post.json")
    doc = json.load(open(tmp_path / "post.json"))
    assert doc["reason"] == "NumericBreakdownError"
    assert "supernode" in doc["detail"]
    assert doc["events"], "postmortem carries no events"
    names = {e["name"] for e in doc["events"]}
    assert {"EQUIL", "COLPERM"} & names            # recent phase spans
    # the error fired INSIDE the FACT phase: it is still on the stack
    stacks = [tuple(s) for st in doc["phase_stack"].values() for s in st]
    assert ("FACT", "phase") in stacks
    assert "compile" in doc and "anchor" in doc


def _mismatch_flight_worker(name, dump_path, q):
    from superlu_dist_tpu.obs import flightrec, trace as trace_mod
    fr = flightrec.FlightRecorder(dump_path, depth=64)
    flightrec.install(fr)
    trace_mod._reset()
    from superlu_dist_tpu.parallel.treecomm import TreeComm
    from superlu_dist_tpu.utils.errors import CollectiveMismatchError
    tc = TreeComm(name, 2, 1, max_len=64, create=False)
    try:
        x = np.ones(8)
        tc.allreduce_sum_any(x)                  # matched prologue
        tc.reduce_sum_any(x)                     # DIVERGES from the owner
        q.put(("no-error", None))
    except CollectiveMismatchError as exc:
        q.put(("mismatch", exc.flightrec_dump))
    finally:
        tc.close()


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_flightrec_dump_on_collective_mismatch_two_ranks(tmp_path,
                                                         monkeypatch):
    """Acceptance: EVERY rank of a diverged 2-rank run leaves its own
    postmortem naming the mismatch — evidence instead of a deadlock."""
    monkeypatch.setenv("SLU_TPU_VERIFY_COLLECTIVES", "1")
    from superlu_dist_tpu.obs import flightrec
    from superlu_dist_tpu.parallel.treecomm import TreeComm
    from superlu_dist_tpu.utils.errors import CollectiveMismatchError

    owner_path = str(tmp_path / "owner.json")
    worker_path = str(tmp_path / "worker.json")
    fr = flightrec.FlightRecorder(owner_path, depth=64)
    prev = flightrec.install(fr)
    trace._reset()
    name = f"/slu_obs_frmm_{os.getpid()}"
    owner = TreeComm(name, 2, 0, max_len=64, create=True)
    ctx = mp.get_context("fork")
    q = ctx.Queue()
    p = ctx.Process(target=_mismatch_flight_worker,
                    args=(name, worker_path, q))
    p.start()
    try:
        x = np.ones(8)
        owner.allreduce_sum_any(x)
        with pytest.raises(CollectiveMismatchError) as ei:
            owner.bcast_any(x)                   # diverges from the worker
        kind, wdump = q.get(timeout=60)
        p.join(timeout=60)
        assert kind == "mismatch", kind
    finally:
        owner.close(unlink=True)
        flightrec.install(prev)
        trace._reset()
    assert ei.value.flightrec_dump == owner_path
    assert wdump == worker_path
    for path in (owner_path, worker_path):
        doc = json.load(open(path))
        assert doc["reason"] == "CollectiveMismatchError"
        assert "reduce_sum_any" in doc["detail"] \
            and "bcast_any" in doc["detail"]
        # the ring caught the matched prologue's comm legs
        assert any(e["cat"] == "comm" for e in doc["events"])
        assert doc["anchor"]["unix_time"] > 0


# ---------------------------------------------------------------------------
# metrics registry (obs/metrics.py)
# ---------------------------------------------------------------------------

def test_metrics_disabled_path_is_noop(tmp_path, monkeypatch):
    from superlu_dist_tpu.obs import metrics

    m = metrics.get_metrics()
    assert m is metrics.NULL_METRICS and not m.enabled
    assert m.inc("x", 1, op="a") is None
    m.set("g", 2.0)
    m.observe("h", 0.1, op="b")
    assert m.snapshot() == {} and m.to_prometheus() == ""
    # singleton: repeated gets allocate nothing new
    assert metrics.get_metrics() is m


def test_metrics_counters_gauges_histograms_and_exports():
    from superlu_dist_tpu.obs import metrics

    m = metrics.Metrics()
    m.inc("slu_comm_bytes_total", 64, op="bcast")
    m.inc("slu_comm_bytes_total", 64, op="bcast")
    m.inc("slu_comm_bytes_total", 8, op="reduce")
    m.set("slu_schedule_groups", 7)
    m.observe("slu_comm_seconds", 0.004, op="bcast")
    m.observe("slu_comm_seconds", 0.2, op="bcast")
    snap = m.snapshot()
    assert snap["counters"]['slu_comm_bytes_total{op="bcast"}'] == 128.0
    assert snap["gauges"]["slu_schedule_groups"] == 7.0
    h = snap["histograms"]['slu_comm_seconds{op="bcast"}']
    assert h["count"] == 2 and abs(h["sum"] - 0.204) < 1e-12
    assert h["min"] == 0.004 and h["max"] == 0.2
    # exports: JSON round-trips; Prometheus text carries samples + types
    assert json.loads(m.to_json()) == snap
    prom = m.to_prometheus()
    assert "# TYPE slu_comm_bytes_total counter" in prom
    assert 'slu_comm_bytes_total{op="bcast"} 128' in prom
    assert 'slu_comm_seconds_count{op="bcast"} 2' in prom
    assert "# TYPE slu_schedule_groups gauge" in prom


def test_metrics_env_gate_and_treecomm_latch(monkeypatch):
    from superlu_dist_tpu.obs import metrics

    monkeypatch.setenv("SLU_TPU_METRICS", "1")
    metrics._reset()
    m = metrics.get_metrics()
    assert isinstance(m, metrics.Metrics) and m.enabled
    m.inc("gate_check", 1)
    assert metrics.get_metrics() is m            # latched


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_metrics_comm_wiring_single_rank(monkeypatch):
    from superlu_dist_tpu.obs import metrics
    from superlu_dist_tpu.parallel.treecomm import TreeComm

    monkeypatch.setenv("SLU_TPU_METRICS", "1")
    metrics._reset()
    name = f"/slu_obs_mw_{os.getpid()}"
    with TreeComm(name, 1, 0, max_len=16, create=True) as tc:
        assert tc._metrics is not None
        tc.bcast(np.ones(8))                     # 8 f64 = 64 bytes
        tc.allreduce_sum(np.ones(4))
    snap = metrics.get_metrics().snapshot()
    assert snap["counters"]['slu_comm_bytes_total{op="bcast"}'] == 64.0
    assert snap["counters"]['slu_comm_calls_total{op="allreduce"}'] == 2.0
    assert 'slu_comm_seconds{op="bcast"}' in snap["histograms"]
    # and with the knob off, TreeComm latches None (one is-None test)
    monkeypatch.delenv("SLU_TPU_METRICS")
    metrics._reset()
    name2 = f"/slu_obs_mw2_{os.getpid()}"
    with TreeComm(name2, 1, 0, max_len=16, create=True) as tc2:
        assert tc2._metrics is None
        tc2.bcast(np.ones(4))


def _metrics_rank_worker(name, q):
    os.environ["SLU_TPU_METRICS"] = "1"
    from superlu_dist_tpu.obs import metrics
    metrics._reset()
    from superlu_dist_tpu.parallel.treecomm import TreeComm
    tc = TreeComm(name, 2, 1, max_len=64, create=False)
    try:
        m = metrics.get_metrics()
        m.inc("test_rank_contrib", 2.0)          # rank 1 contributes 2
        tc.bcast(np.arange(8.0), root=0)
        q.put((1, m.reduce(tc)))
    finally:
        tc.close()


@pytest.mark.skipif(not native.available(),
                    reason="native library unavailable")
def test_metrics_two_rank_reduce_over_treecomm(monkeypatch):
    """Cross-rank aggregation: both ranks call reduce() collectively and
    get the SAME summed/min/max table (the Stats.reduce discipline)."""
    from superlu_dist_tpu.obs import metrics
    from superlu_dist_tpu.parallel.treecomm import TreeComm

    monkeypatch.setenv("SLU_TPU_METRICS", "1")
    metrics._reset()
    name = f"/slu_obs_mr_{os.getpid()}"
    owner = TreeComm(name, 2, 0, max_len=64, create=True)
    try:
        ctx = mp.get_context("spawn")     # no fork of the jax-laden parent
        q = ctx.Queue()
        p = ctx.Process(target=_metrics_rank_worker, args=(name, q))
        p.start()
        m = metrics.get_metrics()
        m.inc("test_rank_contrib", 1.0)          # rank 0 contributes 1
        owner.bcast(np.arange(8.0), root=0)
        mine = m.reduce(owner)
        rank1, theirs = q.get(timeout=120)
        p.join(timeout=120)
        assert p.exitcode == 0
    finally:
        owner.close(unlink=True)
    contrib = mine["counter:test_rank_contrib"]
    assert contrib["sum"] == 3.0
    assert contrib["min"] == 1.0 and contrib["max"] == 2.0
    # both ranks computed the identical table
    assert theirs["counter:test_rank_contrib"] == contrib
    # the wired comm counters aggregated too (1 bcast leg per rank)
    bk = 'counter:slu_comm_calls_total{op="bcast"}'
    assert mine[bk]["sum"] >= 2.0


def test_escalation_ladder_emits_rung_metrics(monkeypatch):
    """A solve that climbs the recovery ladder counts its rung
    transitions in the registry."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.models.gallery import hilbert
    from superlu_dist_tpu.obs import metrics
    from superlu_dist_tpu.utils.options import Options

    monkeypatch.setenv("SLU_TPU_METRICS", "1")
    metrics._reset()
    a = hilbert(12)
    x, lu, stats, info = gssvx(Options(), a, np.ones(a.n_rows))
    assert info == 0
    if stats.solve_report is not None and stats.solve_report.rungs:
        snap = metrics.get_metrics().snapshot()
        rung_keys = [k for k in snap["counters"]
                     if k.startswith("slu_recovery_rungs_total")]
        assert rung_keys, snap["counters"]
        assert sum(snap["counters"][k] for k in rung_keys) \
            == len(stats.solve_report.rungs)


# ---------------------------------------------------------------------------
# bench row: compile_seconds + census + phase_seconds (acceptance fields)
# ---------------------------------------------------------------------------

def test_bench_row_carries_compile_and_phase_fields(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_NX="6",
               BENCH_REPS="1",
               BENCH_DEADLINE_S="240",
               SLU_TPU_FLIGHTREC=str(tmp_path / "bench_fr.json"))
    env.pop("SLU_TPU_TRACE", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, cwd=REPO, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    assert r.returncode == 0, r.stderr.decode()
    row = json.loads(r.stdout.decode().strip().splitlines()[-1])
    assert row["value"] is not None
    assert "compile_seconds" in row and row["compile_seconds"] >= 0
    assert isinstance(row.get("compile_census"), list)
    ph = row["phase_seconds"]
    for phase in ("prepare", "factor-compile", "factor-time"):
        assert phase in ph and ph[phase] >= 0
    assert row["flightrec"] == str(tmp_path / "bench_fr.json")


# ---------------------------------------------------------------------------
# perf-regression gate: self-seeding, pass, regression (fast --row path)
# ---------------------------------------------------------------------------

def _run_gate(history, row_dict, tmp_path):
    row_file = tmp_path / "row.json"
    row_file.write_text(json.dumps(row_dict))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_perf_regress.py"),
         "--row", str(row_file), "--history", str(history)],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def test_perf_gate_seeds_then_passes_then_fails(tmp_path):
    hist = tmp_path / "hist.jsonl"
    row = {"metric": "m_test", "value": 2.0, "backend": "cpu",
           "granularity": "fused", "schedule": "dataflow",
           "blocking": [1, 2, 3], "compile_seconds": 0.5}
    # self-seeding: an empty history passes (acceptance for ci_gates)
    for i in range(3):
        r = _run_gate(hist, row, tmp_path)
        assert r.returncode == 0, r.stderr.decode()
        assert b"SEEDED" in r.stdout
    # at min_samples the gate enforces — an equal value passes
    r = _run_gate(hist, row, tmp_path)
    assert r.returncode == 0 and b"OK" in r.stdout
    # a large drop fails...
    bad = dict(row, value=0.4)
    r = _run_gate(hist, bad, tmp_path)
    assert r.returncode == 1
    assert b"REGRESSION" in r.stdout
    # ...and did NOT poison the baseline (flagged gate_fail)
    r = _run_gate(hist, row, tmp_path)
    assert r.returncode == 0, r.stderr.decode()
    # a different config key keeps its own (empty -> seeding) history
    other = dict(row, backend="tpu")
    r = _run_gate(hist, other, tmp_path)
    assert r.returncode == 0 and b"SEEDED" in r.stdout


def test_mfu_report_prints_compile_section(tmp_path):
    t = trace.Tracer(str(tmp_path / "k.json"))
    t.complete("compile stream._kernel", "compile", 0.0, 1.5,
               key="lu b4 m32 w16 u16", n_args=11, persistent_hit=False)
    t.complete("compile make_factor_fn", "compile", 2.0, 0.5,
               key="fused g7 float32", n_args=2, persistent_hit=True)
    t.close()
    r = _run_mfu(str(tmp_path / "no.jsonl"), str(tmp_path / "k.json"))
    assert r.returncode == 0, r.stderr
    out = r.stdout.decode()
    assert "compile census" in out
    assert "lu b4 m32 w16 u16" in out and "stream._kernel" in out
    assert "[disk hit]" in out

"""slulint v4 program-audit suite (docs/ANALYSIS.md).

Per-rule fixture pairs over real traced programs (donated vs not,
big-const vs argument-passed, matched vs divergent collective sequences
under shard_map), the SLU113 dispatch-loop fixtures, executor-
construction audits on stream/mega/fused/device-solve, a provoked
ProgramAuditError with its flight-recorder postmortem, the incremental
scan cache (warm-hit equivalence, invalidation), and the SARIF
round-trip.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu.analysis import default_rules
from superlu_dist_tpu.analysis.program import (ProgramSpec, audit_spec,
                                               collective_sequence,
                                               trace_spec)
from superlu_dist_tpu.analysis import rules_program as rp
from superlu_dist_tpu.utils import programaudit
from superlu_dist_tpu.utils.errors import ProgramAuditError

pytestmark = pytest.mark.program

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "slulint")

BIG = 1 << 30     # "never fires" threshold for the rule not under test


@pytest.fixture
def fresh_auditor(monkeypatch):
    """SLU_TPU_VERIFY_PROGRAMS=1 with a fresh auditor + clean census
    audit notes, restored afterwards."""
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    monkeypatch.setenv("SLU_TPU_VERIFY_PROGRAMS", "1")
    programaudit._reset()
    with COMPILE_STATS._lock:
        saved = dict(COMPILE_STATS._audits)
        COMPILE_STATS._audits = {}
    yield
    programaudit._reset()
    with COMPILE_STATS._lock:
        COMPILE_STATS._audits = saved


# --------------------------------------------------------------------------
# SLU111 donation/aliasing
# --------------------------------------------------------------------------

def test_slu111_undonated_dead_input_flagged():
    f = jax.jit(lambda x: x + 1.0)
    x = np.zeros(1024, np.float64)
    spec = trace_spec(f, (x,), label="undonated", site="test", dead=(0,))
    findings, stats = audit_spec(spec, donate_min_bytes=1024,
                                 const_max_bytes=BIG)
    assert [f_.rule for f_ in findings] == ["SLU111"]
    assert "not donated" in findings[0].message.lower()
    assert stats["donation_coverage_pct"] == 0.0


def test_slu111_donated_twin_clean():
    f = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    x = np.zeros(1024, np.float64)
    spec = trace_spec(f, (x,), label="donated", site="test", dead=(0,))
    assert spec.donated == (0,)       # read off Traced.args_info
    findings, stats = audit_spec(spec, donate_min_bytes=1024,
                                 const_max_bytes=BIG)
    assert findings == []
    assert stats["donation_coverage_pct"] == 100.0


def test_slu111_small_and_live_inputs_exempt():
    f = jax.jit(lambda x, y: (x * 2.0, y * 3.0))
    x = np.zeros(4, np.float64)          # dead but tiny
    y = np.zeros(4096, np.float64)       # big but live (not declared dead)
    spec = trace_spec(f, (x, y), label="exempt", site="test", dead=(0,))
    findings, _ = audit_spec(spec, donate_min_bytes=1024,
                             const_max_bytes=BIG)
    assert findings == []


# --------------------------------------------------------------------------
# SLU112 baked constants
# --------------------------------------------------------------------------

def test_slu112_closure_captured_const_flagged():
    big = jnp.arange(4096.0)
    f = jax.jit(lambda x: x + big)       # the per-matrix-capture pattern
    spec = trace_spec(f, (np.zeros(4096),), label="baked", site="test")
    findings, stats = audit_spec(spec, donate_min_bytes=BIG,
                                 const_max_bytes=1024)
    assert [f_.rule for f_ in findings] == ["SLU112"]
    assert stats["baked_const_bytes"] >= big.nbytes


def test_slu112_argument_passed_twin_clean():
    f = jax.jit(lambda x, c: x + c)      # the make_factor_fn fix shape
    spec = trace_spec(f, (np.zeros(4096), np.zeros(4096)),
                      label="bucket-closed", site="test")
    findings, stats = audit_spec(spec, donate_min_bytes=BIG,
                                 const_max_bytes=1024)
    assert findings == []
    assert stats["baked_const_bytes"] == 0


# --------------------------------------------------------------------------
# SLU114 SPMD collective lockstep
# --------------------------------------------------------------------------

def _shard_mapped(body, check_vma=True):
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"),
                                 out_specs=P("x"), check_vma=check_vma))


def test_slu114_divergent_branch_collectives_flagged():
    # branches whose outputs differ in varying axes do not trace under
    # the default check, so the divergent program is built the way the
    # SPMD tier builds its own: check_vma=False (parallel/spmd.py)
    def body(a):
        return jax.lax.cond(a.sum() > 0,
                            lambda v: jax.lax.psum(v, "x"),
                            lambda v: v * 1.0, a)

    spec = trace_spec(_shard_mapped(body, check_vma=False), (np.ones(4),),
                      label="divergent", site="test", mesh_axes=("x",))
    findings, _ = audit_spec(spec, donate_min_bytes=BIG,
                             const_max_bytes=BIG)
    assert [f_.rule for f_ in findings] == ["SLU114"]
    assert "divergent" in findings[0].message.lower()


def test_slu114_matched_branch_collectives_clean():
    def body(a):
        return jax.lax.cond(a.sum() > 0,
                            lambda v: jax.lax.psum(v * 2.0, "x"),
                            lambda v: jax.lax.psum(v * 0.5, "x"), a)

    spec = trace_spec(_shard_mapped(body), (np.ones(4),),
                      label="matched", site="test", mesh_axes=("x",))
    findings, _ = audit_spec(spec, donate_min_bytes=BIG,
                             const_max_bytes=BIG)
    assert findings == []
    # the agreed branch sequence is inlined once into the program's
    # collective sequence (psum is psum_invariant under the vma check)
    assert collective_sequence(spec.jaxpr) == [("psum_invariant", ("x",))]
    unchecked = trace_spec(_shard_mapped(body, check_vma=False),
                           (np.ones(4),), label="matched", site="test",
                           mesh_axes=("x",))
    assert collective_sequence(unchecked.jaxpr) == [("psum", ("x",))]


def test_slu114_off_mesh_axis_flagged_on_stub():
    """Axis-consistency check over a duck-typed jaxpr stub (the rules
    are jax-free by design, so a stub is a legal program)."""

    class Prim:
        name = "psum"

    class Eqn:
        primitive = Prim()
        params = {"axes": ("ghost",)}

    class Jaxpr:
        eqns = [Eqn()]

    class Closed:
        jaxpr = Jaxpr()
        consts = ()
        in_avals = ()

    spec = ProgramSpec(label="stub", site="test", jaxpr=Closed(),
                       mesh_axes=("x",))
    findings = rp.audit_collective_lockstep(spec)
    assert [f_.rule for f_ in findings] == ["SLU114"]
    assert "ghost" in findings[0].message


def test_slu114_two_shard_subprocess():
    """A REAL 2-shard shard_map program through the runtime auditor:
    the matched program audits clean and computes the right psum; the
    divergent one raises ProgramAuditError at submit."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["SLU_TPU_VERIFY_PROGRAMS"] = "1"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from superlu_dist_tpu.utils.programaudit import maybe_audit
from superlu_dist_tpu.utils.errors import ProgramAuditError

mesh = Mesh(np.array(jax.devices()[:2]), ("x",))

def matched(a):
    return jax.lax.psum(a, "x")

def divergent(a):
    return jax.lax.cond(a.sum() > 0,
                        lambda v: jax.lax.psum(v, "x"),
                        lambda v: v * 1.0, a)

x = np.arange(8.0)
ok = jax.jit(jax.shard_map(matched, mesh=mesh, in_specs=P("x"),
                           out_specs=P("x")))
maybe_audit("test", "matched", ok, (x,), mesh_axes=("x",))
out = np.asarray(ok(x))
assert np.allclose(out[:4] + out[4:], x[:4] + x[4:] + out[:4]), out

bad = jax.jit(jax.shard_map(divergent, mesh=mesh, in_specs=P("x"),
                            out_specs=P("x"), check_vma=False))
try:
    maybe_audit("test", "divergent", bad, (x,), mesh_axes=("x",))
except ProgramAuditError as e:
    assert "SLU114" in str(e)
    print("AUDIT_RAISED")
else:
    raise SystemExit("divergent 2-shard program audited clean")
"""
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "AUDIT_RAISED" in r.stdout


# --------------------------------------------------------------------------
# SLU113 dispatch-loop host round-trips (source rule, committed fixtures)
# --------------------------------------------------------------------------

def _scan_fixture(name):
    from superlu_dist_tpu.analysis import analyze_source
    path = os.path.join(FIXTURES, name)
    with open(path) as fh:
        return analyze_source(fh.read(), path, default_rules())


def test_slu113_fixture_flagged():
    findings = _scan_fixture("host_roundtrip_loop.py")
    assert sorted({f.rule for f in findings}) == ["SLU113"]
    # float() coercion, np.asarray materialization, bool-coerced test
    assert len([f for f in findings if f.rule == "SLU113"]) == 3


def test_slu113_clean_fixture():
    assert _scan_fixture("device_loop_clean.py") == []


# --------------------------------------------------------------------------
# executor-construction audits (the runtime twin on the real programs)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def analyzed():
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options
    a = poisson2d(7)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym))
    return sf, sym.data[sf.value_perm], a.norm_max()


def _factor(analyzed, executor):
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan
    sf, vals, anorm = analyzed
    plan = build_plan(sf)
    return plan, numeric_factorize(plan, vals, anorm, executor=executor)


def _audit_state():
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    aud = programaudit._AUDITOR
    return aud, COMPILE_STATS.audit_block()


@pytest.mark.parametrize("executor", ["fused", "mega"])
def test_executor_construction_audit(fresh_auditor, analyzed, executor):
    _factor(analyzed, executor)
    aud, blk = _audit_state()
    assert aud is not None and len(aud.audited) > 0
    assert blk["programs"] == len(aud.audited)
    assert blk["findings"] == 0
    assert blk["donation_coverage_pct"] == 100.0
    assert blk["baked_const_bytes"] == 0


def test_stream_and_solve_audit(fresh_auditor, analyzed, monkeypatch):
    # the stream executor audits on census-cold builds only — reset the
    # process-wide censused-key set so this plan's keys count as cold
    from superlu_dist_tpu.numeric import stream
    monkeypatch.setattr(stream, "_CENSUSED_KEYS", set())
    from superlu_dist_tpu.solve.device import DeviceSolver
    plan, fact = _factor(analyzed, "stream")
    aud, _ = _audit_state()
    n_factor = len(aud.audited)
    assert n_factor > 0, "stream executor submitted no programs"
    for fused in (True, False):
        ds = DeviceSolver(fact, fused=fused)
        ds.solve(np.ones((plan.n, 3)))
        ds.solve_trans(np.ones(plan.n))
    aud, blk = _audit_state()
    assert len(aud.audited) > n_factor, "device solve submitted nothing"
    assert blk["findings"] == 0
    assert blk["donation_coverage_pct"] == 100.0
    assert blk["baked_const_bytes"] == 0


def test_off_path_allocates_nothing(analyzed, monkeypatch):
    monkeypatch.delenv("SLU_TPU_VERIFY_PROGRAMS", raising=False)
    programaudit._reset()
    _factor(analyzed, "fused")
    assert programaudit._AUDITOR is None
    assert programaudit.get_auditor() is None


# --------------------------------------------------------------------------
# provoked ProgramAuditError + flight-recorder postmortem
# --------------------------------------------------------------------------

def test_program_audit_error_with_flightrec(tmp_path, monkeypatch):
    from superlu_dist_tpu.obs import flightrec
    dump = tmp_path / "fr-%p.json"
    monkeypatch.setenv("SLU_TPU_FLIGHTREC", str(dump))
    flightrec._reset()
    try:
        aud = programaudit.ProgramAuditor(donate_min_bytes=8,
                                          const_max_bytes=BIG)
        f = jax.jit(lambda x: x * 2.0)
        with pytest.raises(ProgramAuditError) as ei:
            aud.submit("test.site", "undonated", f,
                       (np.zeros(64, np.float64),), dead=(0,))
        err = ei.value
        assert err.rules == ["SLU111"]
        assert err.site == "test.site" and err.program == "undonated"
        assert err.flightrec_dump and os.path.exists(err.flightrec_dump)
        doc = json.load(open(err.flightrec_dump))
        assert doc["reason"] == "ProgramAuditError"
        # the failed program was NOT memoized as audited-clean
        assert ("test.site", "undonated") not in aud.audited
    finally:
        flightrec._reset()


def test_slu112_error_names_capturing_site():
    aud = programaudit.ProgramAuditor(donate_min_bytes=BIG,
                                      const_max_bytes=64)
    big = jnp.arange(512.0)
    f = jax.jit(lambda x: x + big)
    with pytest.raises(ProgramAuditError) as ei:
        aud.submit("stream._kernel", "captured", f, (np.zeros(512),))
    assert "capturing build site" in str(ei.value)
    assert "stream.py" in str(ei.value)


# --------------------------------------------------------------------------
# incremental scan cache
# --------------------------------------------------------------------------

def _run_cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "superlu_dist_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cache_warm_hit_equivalence(tmp_path):
    """Two scans of the same dirty tree: identical findings, second one
    served from the cache."""
    src = tmp_path / "dirty.py"
    src.write_text(open(os.path.join(
        FIXTURES, "host_roundtrip_loop.py")).read())
    cache = str(tmp_path / "cache.json")
    r1 = _run_cli([str(src), "--no-baseline", "--json", "--cache", cache])
    r2 = _run_cli([str(src), "--no-baseline", "--json", "--cache", cache])
    d1, d2 = json.loads(r1.stdout), json.loads(r2.stdout)
    assert r1.returncode == r2.returncode == 1
    assert d1["cache"] == "miss" and d2["cache"] == "hit"
    assert d1["findings"] == d2["findings"] and d1["findings"]


def test_cache_invalidated_on_content_and_ruleset(tmp_path, monkeypatch):
    from superlu_dist_tpu.analysis import cache as sc
    rules = default_rules()
    sources = {"a.py": "x = 1\n"}
    path = str(tmp_path / "c.json")
    sc.store(path, sources, rules, [])
    assert sc.lookup(path, sources, rules) == []
    # content change -> miss
    assert sc.lookup(path, {"a.py": "x = 2\n"}, rules) is None
    # path-set change -> miss
    assert sc.lookup(path, {"a.py": "x = 1\n", "b.py": ""}, rules) is None
    # rule-set / engine version change -> miss
    monkeypatch.setattr(sc, "ANALYSIS_VERSION", "999")
    assert sc.lookup(path, sources, rules) is None


def test_no_cache_flag_writes_nothing(tmp_path):
    src = tmp_path / "clean.py"
    src.write_text("x = 1\n")
    cache = tmp_path / "cache.json"
    r = _run_cli([str(src), "--no-baseline", "--no-cache",
                  "--cache", str(cache)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert not cache.exists()


# --------------------------------------------------------------------------
# SARIF
# --------------------------------------------------------------------------

def test_sarif_roundtrip():
    from superlu_dist_tpu.analysis.sarif import from_sarif, to_sarif
    findings = _scan_fixture("host_roundtrip_loop.py")
    assert findings
    doc = json.loads(json.dumps(to_sarif(findings, default_rules(),
                                         baselined=2)))
    assert doc["version"] == "2.1.0" and "$schema" in doc
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "slulint"
    ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert "SLU113" in ids and "SLU101" in ids
    assert run["properties"]["baselined"] == 2
    back = from_sarif(doc)
    assert [(f.rule, f.path, f.line, f.col, f.message, f.hint)
            for f in back] == \
        [(f.rule, f.path, f.line, f.col, f.message, f.hint)
         for f in sorted(findings,
                         key=lambda f: (f.path, f.line, f.col, f.rule))]


def test_sarif_cli(tmp_path):
    src = tmp_path / "dirty.py"
    src.write_text(open(os.path.join(
        FIXTURES, "host_roundtrip_loop.py")).read())
    r = _run_cli([str(src), "--no-baseline", "--no-cache",
                  "--format", "sarif"])
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["runs"][0]["results"]
    assert all(res["ruleId"] == "SLU113"
               for res in doc["runs"][0]["results"])


# --------------------------------------------------------------------------
# registration plumbing
# --------------------------------------------------------------------------

def test_verify_programs_knob_registered():
    from superlu_dist_tpu.utils.options import KNOB_REGISTRY
    assert "SLU_TPU_VERIFY_PROGRAMS" in KNOB_REGISTRY
    assert KNOB_REGISTRY["SLU_TPU_VERIFY_PROGRAMS"].kind == "flag"


def test_slu113_in_default_rules():
    assert "SLU113" in {r.rule_id for r in default_rules()}

"""slulint v6 sharding & memory-flow suite (docs/ANALYSIS.md).

Per-rule fixture coverage for the source rules (SLU120 mesh/spec
hygiene against the utils/meshreg.py registry, SLU122 dispatch-loop
cross-mesh transfers over the device-taint lattice), the jaxpr rules
over real traced programs (SLU119 implicit-replication blowup through
a REAL 2-shard shard_map subprocess, SLU121 static peak-memory model
validated against XLA's own memory_analysis), the
``SLU_TPU_VERIFY_SHARDING=1`` / ``SLU_TPU_MEM_BUDGET_BYTES`` runtime
auditor (raise-before-run with flight-recorder postmortem, census
``#sharding`` notes, memoization, off-path no-state), the mega
executor's bucket-rung-naming MemoryBudgetError, and the SARIF
round-trip for the four new catalog entries.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu.analysis.core import analyze_sources, default_rules
from superlu_dist_tpu.analysis.program import (ProgramSpec, audit_sharding,
                                               trace_spec)
from superlu_dist_tpu.analysis import rules_sharding as rs
from superlu_dist_tpu.utils import meshreg, programaudit
from superlu_dist_tpu.utils.errors import (MemoryBudgetError,
                                           ShardingAuditError)

pytestmark = pytest.mark.shardlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "slulint")


def _scan(name):
    path = os.path.join("tests", "fixtures", "slulint", name)
    with open(os.path.join(REPO, path)) as f:
        return analyze_sources({path: f.read()})


def _fixture_build(name, *args):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(FIXTURES, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build(*args)


@pytest.fixture
def fresh_sharding_auditor(monkeypatch):
    """SLU_TPU_VERIFY_SHARDING=1 with fresh auditors + clean census
    audit notes, restored afterwards."""
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    monkeypatch.delenv("SLU_TPU_VERIFY_PROGRAMS", raising=False)
    monkeypatch.delenv("SLU_TPU_VERIFY_DTYPES", raising=False)
    monkeypatch.delenv("SLU_TPU_MEM_BUDGET_BYTES", raising=False)
    monkeypatch.setenv("SLU_TPU_VERIFY_SHARDING", "1")
    programaudit._reset()
    with COMPILE_STATS._lock:
        saved = dict(COMPILE_STATS._audits)
        COMPILE_STATS._audits = {}
    yield
    programaudit._reset()
    with COMPILE_STATS._lock:
        COMPILE_STATS._audits = saved


# --------------------------------------------------------------------------
# utils/meshreg: the central axis registry
# --------------------------------------------------------------------------

def test_meshreg_declares_the_grid_axes():
    axes = meshreg.registered_axes()
    assert "snode" in axes and "panel" in axes
    assert meshreg.require_axis("snode") == "snode"
    with pytest.raises(meshreg.UnknownAxisError) as ei:
        meshreg.require_axis("rows")
    assert "rows" in str(ei.value) and "meshreg" in str(ei.value)


def test_process_grid_mesh_axes_come_from_the_registry():
    # parallel/grid.py routes its axis names through require_axis — a
    # registry drift would fail grid construction, not silently diverge
    from superlu_dist_tpu.parallel.grid import gridinit
    g = gridinit(1, 1)
    assert tuple(g.mesh.axis_names) == ("snode", "panel")


# --------------------------------------------------------------------------
# SLU120 mesh/spec hygiene (source)
# --------------------------------------------------------------------------

def test_slu120_fixture_flagged():
    hits = [f for f in _scan("unregistered_axis.py") if f.rule == "SLU120"]
    assert len(hits) == 6, hits
    names = [f for f in hits if "not declared in the mesh-axis registry"
             in f.message]
    # "row", "col" (Mesh), "rows" twice (in_specs + out_specs)
    assert len(names) == 4, hits
    assert any("'row'" in f.message for f in names)
    assert any("'rows'" in f.message for f in names)
    arity = [f for f in hits if "positional argument" in f.message]
    assert len(arity) == 1 and "1 spec(s)" in arity[0].message
    donated = [f for f in hits if "donated argument 1" in f.message]
    assert len(donated) == 1


def test_slu120_fixture_clean():
    assert [f for f in _scan("mesh_clean.py") if f.rule == "SLU120"] == []


def test_slu120_suppression_honored():
    src = ("from jax.sharding import PartitionSpec as P\n"
           "spec = P('bogus')  # slulint: disable=SLU120\n")
    assert [f for f in analyze_sources({"scripts/x.py": src})
            if f.rule == "SLU120"] == []


# --------------------------------------------------------------------------
# SLU122 cross-mesh transfer in dispatch loops (source)
# --------------------------------------------------------------------------

_LOOP_TRANSFER = '''\
import jax
import jax.numpy as jnp

def dispatch(xs, sharding):
    ys = []
    for x in xs:
        y = jnp.sin(x)                    # device value
        moved = jax.device_put(y, sharding)   # flagged: in-loop reshard
        resh = y.reshard(sharding)            # flagged: .reshard()
        ys.append(moved)
        ys.append(resh)
    return ys
'''

_LOOP_UPLOAD = '''\
import numpy as np
import jax

def dispatch(kern, n, sharding):
    ys = []
    for i in range(n):
        pad = np.zeros((8, 8))
        up = jax.device_put(pad, sharding)    # host upload: exempt
        ys.append(kern(up))
    committed = jax.device_put(ys[-1], sharding)  # after the loop: clean
    return ys, committed
'''


def test_slu122_flags_in_loop_device_transfers():
    hits = [f for f in analyze_sources(
        {"superlu_dist_tpu/numeric/fake.py": _LOOP_TRANSFER})
        if f.rule == "SLU122"]
    assert len(hits) == 2, hits
    assert any("`jax.device_put`" in f.message for f in hits)
    assert any("`.reshard()`" in f.message for f in hits)
    assert all("once per group" in f.message for f in hits)


def test_slu122_host_uploads_and_post_loop_transfers_exempt():
    assert [f for f in analyze_sources(
        {"superlu_dist_tpu/solve/fake.py": _LOOP_UPLOAD})
        if f.rule == "SLU122"] == []


def test_slu122_scoped_to_dispatch_packages():
    # the same pattern outside numeric//solve/ is out of scope
    assert [f for f in analyze_sources(
        {"superlu_dist_tpu/obs/fake.py": _LOOP_TRANSFER})
        if f.rule == "SLU122"] == []


# --------------------------------------------------------------------------
# SLU119 implicit replication (jaxpr) — real 2-shard shard_map programs
# --------------------------------------------------------------------------

_SHARD_CHILD = r"""
import importlib.util
import json, os, sys
sys.path.insert(0, os.environ["SLU_REPO"])
import numpy as np
import jax
from jax.sharding import Mesh
from superlu_dist_tpu.utils import programaudit
from superlu_dist_tpu.utils.errors import (MemoryBudgetError,
                                           ShardingAuditError)


def _fixture(name):
    path = os.path.join(os.environ["SLU_REPO"], "tests", "fixtures",
                        "slulint", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


implicit_gather = _fixture("implicit_gather")
sharded_clean = _fixture("sharded_clean")

mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("snode",))
out = {}

fn, args = sharded_clean.build(mesh)
stats = programaudit.maybe_audit("test.shard", "clean", fn, args,
                                 mesh_axes=("snode",))
out["clean"] = {"findings": stats["findings"],
                "peak": stats["peak_bytes_est"],
                "gathers": stats["n_gathers"]}

fn, args = implicit_gather.build(mesh)
try:
    programaudit.maybe_audit("test.shard", "gather", fn, args,
                             mesh_axes=("snode",))
    out["gather"] = {"raised": None}
except MemoryBudgetError:
    out["gather"] = {"raised": "MemoryBudgetError"}
except ShardingAuditError as e:
    out["gather"] = {"raised": "ShardingAuditError", "rules": e.rules,
                     "msg": str(e)}
print(json.dumps(out))
"""


def test_slu119_two_shard_subprocess_flags_gather_passes_sharded():
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               SLU_TPU_VERIFY_SHARDING="1",
               SLU_REPO=REPO)
    env.pop("SLU_TPU_MEM_BUDGET_BYTES", None)
    r = subprocess.run([sys.executable, "-c", _SHARD_CHILD], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["clean"]["findings"] == 0
    assert out["clean"]["peak"] > 0
    assert out["clean"]["gathers"] == 0
    assert out["gather"]["raised"] == "ShardingAuditError"
    assert out["gather"]["rules"] == ["SLU119"]
    assert "all_gather" in out["gather"]["msg"]
    assert "'snode'" in out["gather"]["msg"]


class _StubAval:
    def __init__(self, shape, itemsize=4):
        self.shape = shape
        self.dtype = type("dt", (), {"itemsize": itemsize})()


class _StubVar:
    def __init__(self, shape):
        self.aval = _StubAval(shape)


def _stub_jaxpr(eqns, invars=(), outvars=()):
    return type("J", (), {"eqns": list(eqns), "invars": list(invars),
                          "constvars": [], "outvars": list(outvars)})()


def test_slu119_replicated_constraint_on_mesh_flagged():
    # the fully-replicated device_put/sharding_constraint branch — CPU
    # tracing never produces it, so the duck-typed stub exercises it
    sharding = type("S", (), {"is_fully_replicated": True})()
    eqn = type("E", (), {
        "primitive": type("Pr", (), {"name": "device_put"})(),
        "params": {"devices": [sharding]},
        "invars": [_StubVar((512, 1024))],
        "outvars": [_StubVar((512, 1024))]})()
    spec = ProgramSpec(label="stub", site="test",
                       jaxpr=_stub_jaxpr([eqn]), mesh_axes=("snode",))
    findings, stats = rs.audit_resharding(spec, 1 << 20)
    assert [f.rule for f in findings] == ["SLU119"]
    assert "FULLY-REPLICATED" in findings[0].message
    assert stats["replicated_bytes"] == 512 * 1024 * 4
    # same eqn with no mesh (single-device run): priced, not flagged
    solo = ProgramSpec(label="stub", site="test",
                       jaxpr=_stub_jaxpr([eqn]), mesh_axes=())
    findings, _ = rs.audit_resharding(solo, 1 << 20)
    assert findings == []


# --------------------------------------------------------------------------
# SLU121 static peak-memory model (jaxpr)
# --------------------------------------------------------------------------

def test_slu121_blowup_vs_bounded_fixture_pair():
    fn_b, args_b = _fixture_build("mem_blowup")
    fn_c, args_c = _fixture_build("mem_bounded")
    spec_b = trace_spec(fn_b, args_b, label="blowup", site="test")
    spec_c = trace_spec(fn_c, args_c, label="bounded", site="test")
    _, stats_b = audit_sharding(spec_b, 1 << 20)
    _, stats_c = audit_sharding(spec_c, 1 << 20)
    # everything-live vs free-after-last-use: the walk must see it
    assert stats_b["peak_bytes_est"] >= 2 * stats_c["peak_bytes_est"]
    # a budget between the two verdicts splits the pair
    budget = 3 * 256 * 256 * 4
    f_b, _ = audit_sharding(spec_b, 1 << 20, budget_bytes=budget)
    f_c, _ = audit_sharding(spec_c, 1 << 20, budget_bytes=budget)
    assert [f.rule for f in f_b] == ["SLU121"]
    assert "largest buffers" in f_b[0].message
    assert f_c == []


def test_slu121_estimate_agrees_with_xla_memory_analysis():
    # acceptance: the static model within 2x of XLA's own temp+arg
    # total, where the API is available (CPU backend exposes it)
    fn, args = _fixture_build("mem_blowup")
    spec = trace_spec(fn, args, label="blowup", site="test")
    _, stats = audit_sharding(spec, 1 << 20)
    compiled = fn.lower(*args).compile()
    ma = getattr(compiled, "memory_analysis", lambda: None)()
    if ma is None or not hasattr(ma, "temp_size_in_bytes"):
        pytest.skip("compiled.memory_analysis() not available")
    # temp+arg+output: XLA fuses the elementwise chain so its "temp"
    # bytes are ~0 and the live set sits in args+outputs — the same
    # buffers the liveness walk keeps live to the end
    xla = (int(ma.temp_size_in_bytes) + int(ma.argument_size_in_bytes)
           + int(getattr(ma, "output_size_in_bytes", 0)))
    est = stats["peak_bytes_est"]
    assert xla > 0
    assert xla / 2 <= est <= xla * 2, (est, xla)


def test_slu121_counts_baked_consts():
    big = jnp.arange(1 << 16, dtype=jnp.float32)     # 256 KiB const

    def f(x):
        return jnp.sum(x) + jnp.sum(big)

    spec = trace_spec(jax.jit(f), (np.float32(1.0),),
                      label="const", site="test")
    _, stats = audit_sharding(spec, 1 << 20)
    assert stats["peak_bytes_est"] >= big.nbytes


# --------------------------------------------------------------------------
# runtime twin: SLU_TPU_VERIFY_SHARDING=1 / SLU_TPU_MEM_BUDGET_BYTES
# --------------------------------------------------------------------------

def test_budget_raises_before_run(fresh_sharding_auditor, tmp_path,
                                  monkeypatch):
    from superlu_dist_tpu.obs import flightrec
    monkeypatch.setenv("SLU_TPU_MEM_BUDGET_BYTES", str(64 * 1024))
    monkeypatch.setenv("SLU_TPU_FLIGHTREC", str(tmp_path / "fr-%p.json"))
    programaudit._reset()        # re-latch the budget
    flightrec._reset()
    fn, args = _fixture_build("mem_blowup")
    try:
        with pytest.raises(MemoryBudgetError) as ei:
            programaudit.maybe_audit("test.site", "blowup", fn, args)
        err = ei.value
        assert err.rules == ["SLU121"]
        assert err.site == "test.site" and err.program == "blowup"
        assert err.peak_bytes > err.budget_bytes == 64 * 1024
        # one except covers the whole v6 family
        assert isinstance(err, ShardingAuditError)
        # flight-recorder postmortem dumped at construction
        assert err.flightrec_dump and os.path.exists(err.flightrec_dump)
        doc = json.load(open(err.flightrec_dump))
        assert doc["reason"] == "MemoryBudgetError"
        # the failing program was NOT memoized as audited-clean
        aud = programaudit.get_sharding_auditor()
        assert ("test.site", "blowup") not in aud.audited
        assert aud.findings and aud.findings[0].rule == "SLU121"
    finally:
        flightrec._reset()


def test_budget_alone_implies_the_audit(monkeypatch):
    # a positive byte budget activates the twin without the flag
    monkeypatch.delenv("SLU_TPU_VERIFY_SHARDING", raising=False)
    monkeypatch.setenv("SLU_TPU_MEM_BUDGET_BYTES", str(1 << 30))
    programaudit._reset()
    try:
        aud = programaudit.get_sharding_auditor()
        assert aud is not None and aud.budget_bytes == 1 << 30
    finally:
        programaudit._reset()


def test_clean_program_memoized_with_census_note(fresh_sharding_auditor):
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    fn, args = _fixture_build("mem_bounded")
    s1 = programaudit.maybe_audit("test.site", "bounded", fn, args)
    assert s1["findings"] == 0 and s1["peak_bytes_est"] > 0
    aud = programaudit.get_sharding_auditor()
    assert ("test.site", "bounded") in aud.audited
    # memoized: a second submit returns the same stats, no re-trace
    s2 = aud.submit("test.site", "bounded", None, None)
    assert s2 is s1
    # census note lands under the #sharding-suffixed label and feeds the
    # audit_block aggregates
    assert ("test.site", "bounded#sharding") in COMPILE_STATS._audits
    blk = COMPILE_STATS.audit_block()
    assert blk["programs_sharding_audited"] == 1
    assert blk["peak_bytes_est"] == s1["peak_bytes_est"]
    assert blk["replicated_bytes"] == 0


def test_census_rows_carry_the_memory_column(fresh_sharding_auditor):
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    fn, args = _fixture_build("mem_bounded")
    stats = programaudit.maybe_audit("test.site", "colkey", fn, args)
    mark = COMPILE_STATS.marker()
    with COMPILE_STATS.build("test.site", "colkey"):
        pass
    rows = [r for r in COMPILE_STATS.census(since=mark)
            if r["key"] == "colkey"]
    assert rows and rows[0]["peak_bytes_est"] == stats["peak_bytes_est"]


def test_sharding_off_path_allocates_nothing(monkeypatch):
    monkeypatch.delenv("SLU_TPU_VERIFY_SHARDING", raising=False)
    monkeypatch.delenv("SLU_TPU_MEM_BUDGET_BYTES", raising=False)
    monkeypatch.delenv("SLU_TPU_VERIFY_PROGRAMS", raising=False)
    monkeypatch.delenv("SLU_TPU_VERIFY_DTYPES", raising=False)
    programaudit._reset()
    fn, args = _fixture_build("mem_blowup")    # would breach any budget
    out = programaudit.maybe_audit("test.site", "off", fn, args)
    assert out is None
    assert programaudit._SHARDING_AUDITOR is None
    assert programaudit.get_sharding_auditor() is None


# --------------------------------------------------------------------------
# mega executor: the budget error names the offending bucket RUNG
# --------------------------------------------------------------------------

def test_mega_budget_error_names_the_bucket_rung(monkeypatch):
    from superlu_dist_tpu.models.gallery import poisson2d
    from superlu_dist_tpu.numeric.factor import numeric_factorize
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    a = poisson2d(8)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym))
    plan = build_plan(sf)
    vals = sym.data[sf.value_perm]

    monkeypatch.setenv("SLU_TPU_MEM_BUDGET_BYTES", "4096")
    programaudit._reset()
    try:
        with pytest.raises(MemoryBudgetError) as ei:
            numeric_factorize(plan, vals, a.norm_max(), executor="mega")
        err = ei.value
        assert err.site == "mega._kernel"
        # the label carries the padded pool rung — the axis the budget
        # verdict is actually about
        assert " P" in err.program, err.program
        assert err.peak_bytes > 4096 == err.budget_bytes
    finally:
        programaudit._reset()


# --------------------------------------------------------------------------
# catalog / SARIF plumbing
# --------------------------------------------------------------------------

def test_v6_rules_in_default_rules():
    ids = {r.rule_id for r in default_rules()}
    assert {"SLU119", "SLU120", "SLU121", "SLU122"} <= ids


def test_analysis_version_is_6():
    from superlu_dist_tpu.analysis.core import ANALYSIS_VERSION
    assert ANALYSIS_VERSION == "6"


def test_sarif_catalog_and_roundtrip_for_v6_rules():
    from superlu_dist_tpu.analysis.sarif import from_sarif, to_sarif
    findings = [f for f in _scan("unregistered_axis.py")
                if f.rule == "SLU120"]
    fn, args = _fixture_build("mem_blowup")
    spec = trace_spec(fn, args, label="blowup", site="test")
    f121, _ = audit_sharding(spec, 1 << 20, budget_bytes=4096)
    findings += f121
    assert findings
    doc = json.loads(json.dumps(to_sarif(findings, default_rules())))
    ids = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
    assert {"SLU119", "SLU120", "SLU121", "SLU122"} <= ids
    back = from_sarif(doc)
    assert [(f.rule, f.path, f.line, f.col, f.message, f.hint)
            for f in back] == \
        [(f.rule, f.path, f.line, f.col, f.message, f.hint)
         for f in sorted(findings,
                         key=lambda f: (f.path, f.line, f.col, f.rule))]


def test_sharding_knobs_registered():
    from superlu_dist_tpu.utils.options import KNOB_REGISTRY
    assert KNOB_REGISTRY["SLU_TPU_VERIFY_SHARDING"].kind == "flag"
    assert KNOB_REGISTRY["SLU_TPU_MEM_BUDGET_BYTES"].kind == "int"

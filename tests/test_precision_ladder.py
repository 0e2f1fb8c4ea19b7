"""Throughput-ladder tests: mixed-precision Schur GEMMs with BERR-gated
escalation (ops/dense.gemm_precision, drivers/gssvx gemm-precision rung).

The contract under test (docs/PERFORMANCE.md, throughput ladder):

* every GEMM tier DELIVERS componentwise BERR at or below the gate —
  reduced tiers may escalate (the rung is recorded), but a failing X is
  never returned as converged;
* the executors stay bitwise-identical to each other WITHIN a tier;
* a checkpoint frontier computed at one tier refuses to resume under
  another tier's arithmetic.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from superlu_dist_tpu.drivers.gssvx import gssvx
from superlu_dist_tpu.models.gallery import (
    hilbert, poisson2d, rank_deficient_arrowhead)
from superlu_dist_tpu.numeric.factor import numeric_factorize
from superlu_dist_tpu.numeric.plan import build_plan
from superlu_dist_tpu.ops.dense import (
    GEMM_PREC_LADDER, gemm, gemm_precision, next_gemm_precision)
from superlu_dist_tpu.ordering.dispatch import get_perm_c
from superlu_dist_tpu.sparse.formats import symmetrize_pattern
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
from superlu_dist_tpu.utils.options import KNOB_REGISTRY, Options

pytestmark = pytest.mark.precision


def _analyzed(a, **plan_kw):
    sym = symmetrize_pattern(a)
    co = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, co)
    plan = build_plan(sf, **plan_kw)
    return plan, sym.data[sf.value_perm], a.norm_max()


def _host_fronts(num):
    return [(np.asarray(lp), np.asarray(up)) for lp, up in num.fronts]


# ---------------------------------------------------------------------------
# tier resolution and the helper semantics
# ---------------------------------------------------------------------------

def test_tier_resolution_and_env(monkeypatch):
    monkeypatch.delenv("SLU_TPU_GEMM_PREC", raising=False)
    monkeypatch.delenv("SLU_TPU_PRECISION", raising=False)
    assert gemm_precision() == "default"          # the fast-path default
    assert gemm_precision("bf16") == "bf16"       # explicit wins
    monkeypatch.setenv("SLU_TPU_GEMM_PREC", "f32")
    assert gemm_precision() == "f32"
    # legacy knob interop: an explicitly-set SLU_TPU_PRECISION keeps
    # meaning what it always meant when the new knob is unset
    monkeypatch.delenv("SLU_TPU_GEMM_PREC")
    monkeypatch.setenv("SLU_TPU_PRECISION", "high")
    assert gemm_precision() == "f32"
    monkeypatch.setenv("SLU_TPU_PRECISION", "highest")
    assert gemm_precision() == "highest"
    monkeypatch.setenv("SLU_TPU_GEMM_PREC", "bogus")
    with pytest.raises(ValueError):
        gemm_precision()


def test_ladder_order_and_cpu_noop_steps():
    assert GEMM_PREC_LADDER == ("bf16", "default", "f32", "highest")
    # CPU executes every lax.Precision identically: the only escalation
    # step that changes arithmetic is crossing the bf16 input cast
    assert next_gemm_precision("bf16", backend="cpu") == "default"
    assert next_gemm_precision("default", backend="cpu") is None
    assert next_gemm_precision("highest", backend="cpu") is None
    # accelerators walk every rung
    assert next_gemm_precision("bf16", backend="tpu") == "default"
    assert next_gemm_precision("default", backend="tpu") == "f32"
    assert next_gemm_precision("f32", backend="tpu") == "highest"
    assert next_gemm_precision("highest", backend="tpu") is None


def test_gemm_helper_semantics():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((16, 8)), dtype=jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, 12)), dtype=jnp.float32)
    exact = np.asarray(a) @ np.asarray(b)
    # non-bf16 tiers on CPU are full f32 math (bitwise-identical to one
    # another — CPU ignores lax.Precision) and dtype-preserving
    ref = None
    for tier in ("default", "f32", "highest"):
        out = gemm(a, b, tier)
        assert out.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out), exact, rtol=1e-4)
        if ref is None:
            ref = np.asarray(out)
        else:
            assert (np.asarray(out) == ref).all()
    # bf16 tier truncates inputs but accumulates at f32 and returns f32
    out = gemm(a, b, "bf16")
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), exact, rtol=2e-2,
                               atol=2e-2)
    assert float(np.max(np.abs(np.asarray(out) - exact))) > 0.0
    # complex operands have no bf16 carrier: degrade to default, exact
    ac = a.astype(jnp.complex64)
    bc = b.astype(jnp.complex64)
    outc = gemm(ac, bc, "bf16")
    assert outc.dtype == jnp.complex64
    np.testing.assert_allclose(np.asarray(outc).real, exact, rtol=1e-4,
                               atol=1e-5)


def test_new_knobs_registry_routed():
    """SLU104 satellite: the ladder knobs are registry-declared, so the
    slulint env rule covers their reads (the tree scans clean)."""
    for name in ("SLU_TPU_GEMM_PREC", "SLU_TPU_PEAK_GFLOPS"):
        assert name in KNOB_REGISTRY, name


# ---------------------------------------------------------------------------
# delivered accuracy: BERR <= gate at every tier, escalation recorded
# ---------------------------------------------------------------------------

GALLERY = (
    ("poisson", lambda: poisson2d(12)),
    ("hilbert", lambda: hilbert(8)),
    ("arrowhead", lambda: rank_deficient_arrowhead(n=60, delta=1e-6,
                                                   seed=0)),
)


@pytest.mark.parametrize("tier", ["bf16", "f32", "highest"])
@pytest.mark.parametrize("name,make", GALLERY, ids=[g[0] for g in GALLERY])
def test_delivered_berr_every_tier(name, make, tier):
    """Gallery × tier: whatever the tier gambles, the DELIVERED berr
    meets the gate (escalation allowed and recorded — never a failing X
    reported converged)."""
    a = make()
    xt = np.random.default_rng(1).standard_normal(a.n_rows)
    b = a.matvec(xt)
    x, lu, stats, info = gssvx(Options(gemm_prec=tier,
                                       factor_dtype="float32"), a, b)
    assert info == 0
    rep = stats.solve_report
    assert np.all(np.isfinite(x))
    assert rep.converged and rep.berr is not None
    assert rep.berr <= rep.target, rep.summary()
    # the report names the tier the ANSWER rests on (post-escalation)
    assert rep.gemm_precision in GEMM_PREC_LADDER


def test_escalation_rung_fires_on_hilbert_bf16():
    """hilbert(8) at the bf16 tier misses the f64-class gate on the raw
    factors: the gemm-precision rung must fire, be recorded, and the
    ladder must still deliver a converged answer."""
    a = hilbert(8)
    b = a.matvec(np.ones(a.n_rows))
    x, lu, stats, info = gssvx(Options(gemm_prec="bf16",
                                       factor_dtype="float32"), a, b)
    assert info == 0
    rep = stats.solve_report
    names = [r.name for r in rep.rungs]
    assert "gemm-precision" in names, rep.summary()
    assert rep.converged and rep.berr <= rep.target, rep.summary()
    # the adopted handle is the escalated one, and the report reflects
    # what the answer actually rests on (tier and/or dtype moved up)
    assert (rep.gemm_precision != "bf16"
            or rep.factor_dtype != "float32"), rep.summary()


def test_norefine_still_gated_on_reduced_tier():
    """Opting out of IR is not opting out of the BERR gate: NOREFINE at
    a reduced tier still probes componentwise berr and escalates on a
    miss (check_precision_safety.py gate, phase A twin)."""
    from superlu_dist_tpu.utils.options import IterRefine
    a = hilbert(8)
    b = a.matvec(np.ones(a.n_rows))
    x, lu, stats, info = gssvx(
        Options(gemm_prec="bf16", factor_dtype="float32",
                iter_refine=IterRefine.NOREFINE), a, b)
    assert info == 0
    rep = stats.solve_report
    assert rep.berr is not None and rep.target is not None
    assert rep.converged and rep.berr <= rep.target, rep.summary()
    assert rep.rungs, "reduced-tier NOREFINE miss must escalate"


def test_well_conditioned_fast_tier_no_rungs():
    """The fast path on a well-conditioned system converges with ZERO
    ladder actions — the gamble costs nothing when it pays off."""
    a = poisson2d(12)
    b = a.matvec(np.ones(a.n_rows))
    x, lu, stats, info = gssvx(Options(gemm_prec="bf16"), a, b)
    assert info == 0
    rep = stats.solve_report
    assert rep.converged and rep.rungs == []
    assert rep.gemm_precision == "bf16"


# ---------------------------------------------------------------------------
# executor equivalence per tier
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tier", ["bf16", "highest"])
def test_bitwise_mega_stream_fused_per_tier(tier):
    a = poisson2d(14)
    plan, vals, anorm = _analyzed(a, closed=True)
    outs = {}
    for ex in ("fused", "stream", "mega"):
        num = numeric_factorize(plan, vals, anorm, dtype="float32",
                                executor=ex, gemm_prec=tier)
        assert num.gemm_prec == tier
        outs[ex] = _host_fronts(num)
    for ex in ("stream", "mega"):
        for (bl, bu), (ol, ou) in zip(outs["fused"], outs[ex]):
            assert (bl == ol).all() and (bu == ou).all(), \
                f"{ex} != fused at tier {tier}"


def test_tiers_actually_differ_bf16():
    """bf16 vs highest factors of the same plan must NOT be bitwise
    equal — otherwise the ladder is a no-op and the 3x is fiction."""
    a = poisson2d(14)
    plan, vals, anorm = _analyzed(a)
    hi = _host_fronts(numeric_factorize(plan, vals, anorm,
                                        dtype="float32",
                                        executor="fused",
                                        gemm_prec="highest"))
    lo = _host_fronts(numeric_factorize(plan, vals, anorm,
                                        dtype="float32",
                                        executor="fused",
                                        gemm_prec="bf16"))
    assert any((h[0] != l[0]).any() or (h[1] != l[1]).any()
               for h, l in zip(hi, lo))


# ---------------------------------------------------------------------------
# checkpoint identity + peak table
# ---------------------------------------------------------------------------

def test_checkpoint_refuses_cross_tier_resume(tmp_path):
    from superlu_dist_tpu.persist.checkpoint import (
        FactorCheckpointer, load_checkpoint)
    from superlu_dist_tpu.utils.errors import CheckpointMismatchError
    a = poisson2d(8)
    plan, vals, anorm = _analyzed(a)
    thresh = np.float32(1e-8)
    ck = FactorCheckpointer(str(tmp_path), plan, vals.astype(np.float32),
                            thresh, "float32", gemm_prec="bf16")
    ck.flush(0, [], np.zeros(plan.pool_size, np.float32), 0,
             reason="test")
    ck.complete(cleanup=False)
    st = load_checkpoint(str(tmp_path), plan=plan,
                         pattern_values=vals.astype(np.float32),
                         thresh=thresh, dtype="float32",
                         gemm_prec="bf16")
    assert st.k == 0
    with pytest.raises(CheckpointMismatchError):
        load_checkpoint(str(tmp_path), plan=plan,
                        pattern_values=vals.astype(np.float32),
                        thresh=thresh, dtype="float32",
                        gemm_prec="highest")


def test_peak_detection_and_mfu(monkeypatch):
    from superlu_dist_tpu.utils.peaks import (
        detect_peak_gflops, mfu_pct, table_peak_gflops)
    monkeypatch.setenv("SLU_TPU_PEAK_GFLOPS", "1000")
    peak, src = detect_peak_gflops("default")
    assert peak == 1000.0 and src == "env"
    pct, p, s = mfu_pct(10.0, "default")
    assert pct == 1.0
    monkeypatch.delenv("SLU_TPU_PEAK_GFLOPS")
    # CPU backend: measured calibration, never the TPU constant
    peak, src = detect_peak_gflops("default")
    assert peak > 0 and src.startswith("measured:")
    pct, _, _ = mfu_pct(peak / 100.0, "default")
    assert pct > 0.0         # never rounds a real rate down to 0.0
    # jax-free table accessor keyed by the device_kind a v5e reports:
    # tier pass-counts divide the bf16 peak, an unknown kind raises
    assert table_peak_gflops("TPU v5 lite", "bf16") == 197_000.0
    assert table_peak_gflops("TPU v5 lite", "highest") == pytest.approx(
        197_000.0 / 6)
    for unknown in ("A100", "TPU v5e", "tpu"):
        with pytest.raises(KeyError):
            table_peak_gflops(unknown, "bf16")


def test_bench_history_key_is_precision_tagged():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench_history", os.path.join(os.path.dirname(__file__), "..",
                                      "scripts", "bench_history.py"))
    bh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bh)
    base = {"metric": "m", "backend": "cpu", "granularity": "fused",
            "schedule": "dataflow", "blocking": [1, 2]}
    k_hi = bh.row_key({**base, "gemm_precision": "highest"})
    k_lo = bh.row_key({**base, "gemm_precision": "bf16"})
    assert k_hi != k_lo      # no cross-precision baselines

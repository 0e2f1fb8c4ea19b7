"""Per-backend / per-precision peak-FLOP tables for honest MFU.

The bench's historical MFU denominator was one constant
(``BENCH_PEAK_F32_TFLOPS`` = 49 TFLOP/s, a v5e figure): every CPU row
divided a few GFLOP/s by a TPU peak and printed ``mfu_pct: 0.0`` — a
number that *looks* measured and is pure noise.  This module owns the
denominator instead:

* ``SLU_TPU_PEAK_GFLOPS`` (registered knob) overrides everything — the
  operator's calibrated figure wins;
* TPU backends look up a per-device-kind, per-GEMM-tier table
  (``TPU_PEAK_GFLOPS`` — vendor bf16 figures keyed by the exact
  ``device_kind`` jax reports; the ``f32``/``highest`` tiers divide by
  the 3-/6-pass MXU cost, the ``default``/``bf16`` tiers run at the
  native single-pass rate).  A kind missing from the table raises —
  never a borrowed figure;
* the CPU backend CALIBRATES: one cached micro-GEMM per tier, timed at
  steady state — a measured machine-local peak instead of a borrowed
  constant.

Every consumer reports the peak's provenance alongside the percentage
(``peak_source``), so an MFU number can always be traced to the
denominator it was computed against.  ``table_peak_gflops`` is the
jax-free accessor for offline tooling (scripts/mfu_report.py) reading
rows recorded on another machine.
"""

from __future__ import annotations

import functools

from superlu_dist_tpu.utils.options import env_float

#: vendor peak dense-matmul throughput in GFLOP/s per TPU chip at the
#: bf16 native rate, keyed by jax's ``device_kind`` exactly as a device
#: reports it; reduced-precision tiers derive from it via the MXU pass
#: counts (default/bf16 = 1 pass, f32 = 3, highest = 6).  Source: Google
#: Cloud TPU documentation, one page per generation ("TPU v5e": 197
#: TFLOP/s bf16; "TPU v5p": 459; "TPU v4": 275; "TPU v6e": 918).
TPU_PEAK_GFLOPS = {
    "TPU v5 lite": 197_000.0,     # v5e
    "TPU v5": 459_000.0,          # v5p
    "TPU v4": 275_000.0,
    "TPU v6 lite": 918_000.0,     # v6e
}

#: MXU passes per GEMM tier (ops/dense.GEMM_PREC_LADDER semantics)
TIER_PASSES = {"bf16": 1, "default": 1, "f32": 3, "highest": 6}


def table_peak_gflops(device_kind: str, gemm_precision: str) -> float:
    """Tabulated TPU peak for one device kind + GEMM tier.  Pure table
    lookup — no jax import — for offline row post-processing
    (scripts/mfu_report.py).  A kind the table does not hold raises
    KeyError."""
    if device_kind not in TPU_PEAK_GFLOPS:
        raise KeyError(f"no peak for device_kind {device_kind!r}: the "
                       f"table (utils/peaks.py) holds "
                       f"{sorted(TPU_PEAK_GFLOPS)}")
    return TPU_PEAK_GFLOPS[device_kind] / TIER_PASSES.get(gemm_precision, 6)


@functools.lru_cache(maxsize=None)
def _calibrate_gflops(tier: str) -> float:
    """Measured matmul peak of THIS process's default backend at one
    GEMM tier: a steady-state timed micro-GEMM through the same
    ``ops.dense.gemm`` wrapper the factor path uses.  Cached per tier —
    one-shot cost (~100 ms) per process."""
    import time

    import jax
    import jax.numpy as jnp

    from superlu_dist_tpu.ops.dense import gemm

    n = 512
    a = jnp.ones((n, n), dtype=jnp.float32)
    fn = jax.jit(lambda x, y: gemm(x, y, tier))
    jax.block_until_ready(fn(a, a))          # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(a, a))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n ** 3 / max(best, 1e-9) / 1e9


def detect_peak_gflops(gemm_precision: str,
                       backend: str | None = None) -> tuple[float, str]:
    """Resolve the MFU denominator for this process: ``(gflops,
    source)`` where source names the provenance ("env", "table:<kind>",
    or "measured:<backend>").  ``SLU_TPU_PEAK_GFLOPS`` wins when set;
    TPU backends read the vendor table (an unknown kind raises);
    everything else calibrates."""
    override = env_float("SLU_TPU_PEAK_GFLOPS")
    if override > 0:
        return float(override), "env"
    import jax
    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        kind = jax.devices()[0].device_kind
        return table_peak_gflops(kind, gemm_precision), f"table:{kind}"
    return _calibrate_gflops(gemm_precision), f"measured:{backend}"


def mfu_pct(gflops: float, gemm_precision: str,
            backend: str | None = None) -> tuple[float, float, str]:
    """(mfu_pct, peak_gflops, source) for an achieved rate — rounded to
    4 decimals so small-but-real utilizations never print as 0.0 (the
    historical honesty bug this module replaces)."""
    peak, source = detect_peak_gflops(gemm_precision, backend=backend)
    return round(100.0 * gflops / max(peak, 1e-9), 4), peak, source

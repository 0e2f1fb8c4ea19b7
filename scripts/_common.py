"""Shared setup for the CPU-backend measurement scripts in this
directory (config4_virtual, df64_scale, pgssvx_scale).

Not used by the TPU-session scripts (baseline_fixtures_tpu,
df64_cost_tpu) — those must NOT pin the CPU platform.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_session(n_devices: int = 1, x64: bool = True):
    """Pin the CPU platform (with `n_devices` virtual devices), enable
    x64, and point jax at the persistent compile cache.  Must run before
    the first jax operation; any XLA_FLAGS the caller needs go into the
    environment BEFORE this call (backend init snapshots them).
    Returns the configured jax module."""
    sys.path.insert(0, REPO)
    import jax
    jax.config.update("jax_platforms", "cpu")
    if n_devices > 1:
        jax.config.update("jax_num_cpu_devices", n_devices)
    if n_devices > 1 and len(jax.devices()) < n_devices:
        raise SystemExit(
            f"cpu_session: wanted {n_devices} virtual cpu devices, got "
            f"{len(jax.devices())} — backend initialized before this call?")
    if x64:
        jax.config.update("jax_enable_x64", True)
    from superlu_dist_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()
    return jax


def raise_collective_timeouts():
    """Raise the XLA:CPU in-process collective rendezvous timeouts (the
    r3 rc=134 lesson: 8-thread all-gathers on big arrays legitimately
    take minutes on one core).  Must run BEFORE cpu_session / backend
    init — XLA snapshots XLA_FLAGS there."""
    import os
    if "collective_call_terminate_timeout" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=3600"
            + " --xla_cpu_collective_call_terminate_timeout_seconds=14400")


def parse_mesh_spec(spec: str):
    """'1' -> (1, 1, 1); 'RxC' (R*C >= 2) -> (R, C, R*C); else SystemExit."""
    import re
    if spec == "1":
        return 1, 1, 1
    m = re.fullmatch(r"(\d+)x(\d+)", spec)
    if m:
        r, c = int(m.group(1)), int(m.group(2))
        if r * c >= 2:
            return r, c, r * c
    raise SystemExit(f"mesh spec {spec!r}: expected '1' (single device) "
                     "or 'RxC' with R*C >= 2 (e.g. '4x2')")

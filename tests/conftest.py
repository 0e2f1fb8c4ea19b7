"""Test harness configuration.

Tests run on the CPU backend with an 8-device virtual mesh so multi-chip
sharding is exercised without TPU hardware (the driver separately dry-runs
the multi-chip path), and with x64 enabled so the f64/c128 reference paths
are exact.  Mirrors the reference's strategy of oversubscribing MPI ranks on
one box (SURVEY.md §4, .travis_tests.sh).

The CPU pin is also written to the environment so that subprocesses a
test starts inherit it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"   # for any subprocesses
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# the dryrun's n=1e5 pool-partition phase duplicates
# tests/test_pool_partition.py (~4 compile-minutes); run it only in the
# driver's standalone dryrun, not again inside the suite
os.environ.setdefault("SLU_TPU_DRYRUN_BIG", "0")

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


@pytest.fixture
def fresh_solve_programs():
    """Drop the process-wide solve-program caches (the fused sweeps and
    the refinement SpMV), so that the test's first solver or operator
    builds its programs whatever ran earlier in this process."""
    from superlu_dist_tpu.parallel import dist
    from superlu_dist_tpu.solve import device
    for builder in (device._fused_kernels, device._fused_trans_kernels,
                    dist._refine_spmv):
        builder.cache_clear()

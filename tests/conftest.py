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

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

"""Compile rehearsals for a TPU v5e, without the chip.

The TPU compiler is installed with jax, and compiles for a described
``v5e:2x2`` topology that is not attached: what it refuses here — a
kernel it cannot lower, a program that does not fit the chip's memory,
a mesh program it cannot partition — it would refuse on the chip.
Nothing runs, so these say nothing about results or times.

Covered, on the main path of ``chip_smoke.py``:

the n=110,592 poisson3d plan with the TPU blocking —

* its streamed factor kernels, largest shape keys first;
* its fused device-solve sweep programs (solve/device.DeviceSolver);
* the f64 device SpMV of the refinement residual (parallel/dist.py);
* its SPMD factor group programs (parallel/spmd.SpmdFactorExecutor),
  largest first, and the SPMD solve sweep (parallel/spmd.SpmdSolver) on
  a four-device mesh of the described chips.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and a worker that
cannot must skip these tests, not fail to collect them.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from superlu_dist_tpu.drivers.gssvx import analyze
from superlu_dist_tpu.models.gallery import poisson3d
from superlu_dist_tpu.utils.options import Options

#: HBM of one v5e chip (Google Cloud TPU documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10 ** 9

#: the TPU blocking chip_smoke.py runs with
BLOCKING = dict(relax=256, max_supernode=1024, min_bucket=32,
                bucket_growth=1.3, amalg_tol=1.2)

#: how many of the n=110,592 plan's largest kernels to compile, per tier
N_LARGEST = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # libtpu logs under /tmp unless told otherwise
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but not read back without one: keep the cache out of it
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("snode", "panel"))


@pytest.fixture(scope="module")
def plan_n110592():
    lu, _, _ = analyze(Options(**BLOCKING), poisson3d(48))
    assert lu.plan.n == 110_592
    return lu.plan


@pytest.fixture(scope="module")
def stream_n110592(plan_n110592):
    """The streamed executor of the chip smoke's plan, and its distinct
    kernels (first call tuple of each), largest first."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    plan = plan_n110592
    ex = StreamExecutor(plan, "float32")
    first = {}
    for key, a, child_arrs, _, _ in ex._steps:
        first.setdefault(key, (*a, *child_arrs))
    keys = sorted(first, key=lambda k: -k[0][0] * k[0][1] ** 2)
    return plan, first, keys


def _fits(compiled):
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert peak < V5E_HBM_BYTES, (peak, m)
    return peak


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: None if x is None
        else jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree, is_leaf=lambda x: x is None)


@pytest.mark.parametrize("rank", range(N_LARGEST))
def test_stream_kernel_compiles_for_v5e(rank, one_chip, stream_n110592):
    """The rank-th largest group kernel of the n=110,592 plan compiles
    for one v5e and its program fits the chip."""
    from superlu_dist_tpu.numeric.stream import _kernel
    from superlu_dist_tpu.ops.dense import gemm_precision, pivot_kernel
    plan, first, keys = stream_n110592
    key = keys[rank]
    dt = jnp.float32
    args = (jax.ShapeDtypeStruct((len(plan.pattern_indices),), dt),
            jax.ShapeDtypeStruct((plan.pool_size,), dt),
            jax.ShapeDtypeStruct((), dt), *first[key])
    fn = _kernel(*key, None, False, pivot_kernel(), gemm_precision(None))
    compiled = fn.lower(*_abstract(args, one_chip)).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def factors_n110592(plan_n110592):
    """Zero f32 factors of the n=110,592 plan: the solvers' programs
    depend only on their shapes."""
    from superlu_dist_tpu.numeric.factor import NumericFactorization
    plan = plan_n110592
    fronts = [(np.zeros((g.batch, g.m, g.w), np.float32),
               np.zeros((g.batch, g.w, g.u), np.float32))
              for g in plan.groups]
    return NumericFactorization(plan=plan, fronts=fronts, tiny_pivots=0,
                                dtype=jnp.dtype("float32"))


@pytest.mark.parametrize("sweep", ["fwd", "bwd"])
def test_device_solve_sweep_compiles_for_v5e(sweep, one_chip,
                                             factors_n110592):
    """The fused forward/backward sweep programs of the device solve at
    n=110,592 (one program per sweep per nrhs bucket) compile for one
    v5e."""
    from superlu_dist_tpu.solve.device import DeviceSolver
    plan = factors_n110592.plan
    solver = DeviceSolver(factors_n110592)
    assert solver.fused
    fwd, bwd = solver._fused_fns()
    x = jax.ShapeDtypeStruct((plan.n + 1, 1), jnp.float32, sharding=one_chip)
    panels = _abstract(solver.fronts, one_chip)
    idx = _abstract([(f, r, w) for _, f, r, w in solver._groups], one_chip)
    invs = solver._invs
    if sweep == "fwd":
        compiled = fwd.lower(x, x, panels, idx, invs).compile()
    else:
        compiled = bwd.lower(x, panels, idx, invs).compile()
    _fits(compiled)


def test_device_spmv_f64_compiles_for_v5e(one_chip):
    """The refinement residual's f64 SpMV at n=110,592 compiles for one
    v5e (XLA emulates f64 there)."""
    from superlu_dist_tpu.parallel.dist import DeviceSpMV
    a = poisson3d(48)
    spmv = DeviceSpMV(a, dtype=np.float64)
    x = jax.ShapeDtypeStruct((a.n_rows, 1), jnp.float64, sharding=one_chip)
    compiled = spmv._fn.lower(
        *_abstract((spmv._vals, spmv._rows, spmv._cols), one_chip),
        x).compile()
    _fits(compiled)


@pytest.fixture(scope="module")
def spmd_n110592(mesh4, plan_n110592):
    """The SPMD executor of the chip smoke's plan on the described 2x2
    mesh, and its group programs (first step of each), largest first."""
    from superlu_dist_tpu.parallel.spmd import SpmdFactorExecutor
    ex = SpmdFactorExecutor(plan_n110592, "float32", mesh4)
    first = {}
    for key, args in ex._steps:
        first.setdefault(key, args)
    keys = sorted(first, key=lambda k: -k[0][0] * k[0][1] ** 2)
    return ex, first, keys


@pytest.mark.parametrize("rank", range(N_LARGEST))
def test_spmd_factor_compiles_on_four_chips(rank, mesh4, spmd_n110592):
    """The rank-th largest SPMD factor group program of the n=110,592
    plan compiles for a 2x2 mesh of described v5e chips: the shard_map,
    its all-gathers and psum partition, and each chip's share fits its
    memory."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ex, first, keys = spmd_n110592
    key = keys[rank]
    plan, rep = ex.plan, NamedSharding(mesh4, P())
    head = (jax.ShapeDtypeStruct((len(plan.pattern_indices),), jnp.float32,
                                 sharding=rep),
            jax.ShapeDtypeStruct((plan.pool_size,), jnp.float32,
                                 sharding=rep),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep))
    args = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype,
                                      sharding=NamedSharding(mesh4, spec))
                 for x, spec in zip(first[key], key[2]))
    compiled = ex._programs[key].lower(*head, *args).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo


def test_spmd_solve_compiles_on_four_chips(mesh4, factors_n110592):
    """The SPMD solve's one fwd+bwd sweep program at n=110,592 compiles
    for the described 2x2 mesh and fits each chip."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from superlu_dist_tpu.parallel.spmd import SpmdSolver
    solver = SpmdSolver(factors_n110592, mesh4)
    n1 = factors_n110592.plan.n + 1
    x = jax.ShapeDtypeStruct((n1, 1), jnp.float32,
                             sharding=NamedSharding(mesh4, P()))
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh4, spec))
            for a, spec in zip(solver._spmd_flat, solver._spmd_specs)]
    compiled = solver._spmd_program(None).lower(x, x, *args).compile()
    _fits(compiled)
    assert "all-gather" in compiled.as_text()

"""Multi-device sharded factorization on the virtual 8-device CPU mesh.

Mirrors the reference's strategy of oversubscribing MPI ranks on one box
(.travis_tests.sh) to test multi-process behavior; here the "ranks" are
XLA virtual devices in a jax.sharding.Mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from superlu_dist_tpu.models.gallery import poisson2d
from superlu_dist_tpu.sparse.formats import symmetrize_pattern
from superlu_dist_tpu.utils.options import Options
from superlu_dist_tpu.ordering.dispatch import get_perm_c
from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
from superlu_dist_tpu.numeric.plan import build_plan
from superlu_dist_tpu.numeric.factor import make_factor_fn
from superlu_dist_tpu.parallel.grid import gridinit


def _plan(n_grid=12):
    a = poisson2d(n_grid)
    opts = Options()
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(opts, a, sym)
    sf = symbolic_factorize(sym, col_order, relax=opts.relax,
                            max_supernode=opts.max_supernode)
    plan = build_plan(sf)
    avals = sym.data[sf.value_perm]
    thresh = np.sqrt(np.finfo(np.float64).eps) * a.norm_max()
    return plan, avals, thresh


def test_eight_devices_visible():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("shape", [(4, 2), (2, 2), (8, 1)])
@pytest.mark.slow
def test_sharded_factor_matches_single_device(shape):
    plan, avals, thresh = _plan()
    single = make_factor_fn(plan, "float64")
    ref_fronts, ref_tiny = single(jnp.asarray(avals),
                                  jnp.asarray(thresh))
    grid = gridinit(*shape)
    fn = make_factor_fn(plan, "float64", mesh=grid.mesh)
    fronts, tiny = fn(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(tiny) == int(ref_tiny)
    for (lp, up), (rlp, rup) in zip(fronts, ref_fronts):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(up), np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_stream_matches_fused():
    plan, avals, thresh = _plan()
    fused = make_factor_fn(plan, "float64")
    rf, rt = fused(jnp.asarray(avals), jnp.asarray(thresh))
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    ex = StreamExecutor(plan, "float64")
    gf, gt = ex(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(gt) == int(rt)
    for (lp, up), (rlp, rup) in zip(gf, rf):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(rlp))
        np.testing.assert_array_equal(np.asarray(up), np.asarray(rup))


@pytest.mark.parametrize("shape", [(4, 2), (8, 1)])
@pytest.mark.slow
def test_sharded_stream_matches_single(shape):
    """The real-TPU executor must shard (VERDICT r1 gap #3): streamed
    per-bucket kernels under a mesh == single-device stream, bit-equal."""
    plan, avals, thresh = _plan()
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    single = StreamExecutor(plan, "float64")
    rf, rt = single(jnp.asarray(avals), jnp.asarray(thresh))
    grid = gridinit(*shape)
    ex = StreamExecutor(plan, "float64", mesh=grid.mesh)
    gf, gt = ex(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(gt) == int(rt)
    for (lp, up), (rlp, rup) in zip(gf, rf):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(up), np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_gssvx_with_grid_matches_serial():
    """The driver accepts a ProcessGrid (pdgssvx's gridinfo_t argument):
    full pipeline sharded over the mesh == single-device result."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.utils.options import Options
    a = poisson2d(11)
    xt = np.random.default_rng(6).standard_normal(a.n_rows)
    b = a.matvec(xt)
    x0, _, _, info0 = gssvx(Options(), a, b)
    grid = gridinit(4, 2)
    x1, lu1, stats1, info1 = gssvx(Options(), a, b, grid=grid)
    assert info0 == info1 == 0
    np.testing.assert_allclose(x1, x0, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(x1, xt, rtol=1e-8, atol=1e-8)


@pytest.mark.slow
def test_device_solve_on_sharded_factors():
    """The pdgstrs analog must work when the factors live sharded on the
    mesh (solve after a multi-chip factorization, no host round-trip)."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    from superlu_dist_tpu.numeric.factor import NumericFactorization
    from superlu_dist_tpu.solve.device import DeviceSolver
    from superlu_dist_tpu.solve.trisolve import lu_solve
    plan, avals, thresh = _plan(10)
    grid = gridinit(4, 2)
    ex = StreamExecutor(plan, "float64", mesh=grid.mesh)
    fronts, tiny = ex(jnp.asarray(avals), jnp.asarray(thresh))
    fact = NumericFactorization(plan=plan, fronts=list(fronts),
                                tiny_pivots=int(tiny),
                                dtype=jnp.dtype("float64"))
    rng = np.random.default_rng(0)
    d = rng.standard_normal((plan.n, 2))
    got = DeviceSolver(fact).solve(d)
    want = lu_solve(fact, d)
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)


def test_graft_dryrun():
    import importlib.util
    import os
    path = os.path.join(os.path.dirname(__file__), "..", "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


@pytest.mark.slow
def test_pool_partitioned_stream_matches_replicated():
    """Sharding the Schur pool itself across the mesh (the n≈1M memory
    path: ~27 GB pool > one chip's HBM) must be bit-equal to the
    replicated-pool stream."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    plan, avals, thresh = _plan()
    ref = StreamExecutor(plan, "float64")(jnp.asarray(avals),
                                          jnp.asarray(thresh))
    grid = gridinit(4, 2)
    ex = StreamExecutor(plan, "float64", mesh=grid.mesh,
                        pool_partition=True)
    got = ex(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(got[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(got[0], ref[0]):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(up), np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


def test_pool_partitioned_fused_matches_replicated():
    from superlu_dist_tpu.numeric.factor import make_factor_fn
    plan, avals, thresh = _plan()
    ref = make_factor_fn(plan, "float64")(jnp.asarray(avals),
                                          jnp.asarray(thresh))
    grid = gridinit(8, 1)
    fn = make_factor_fn(plan, "float64", mesh=grid.mesh,
                        pool_partition=True)
    got = fn(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(got[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(got[0], ref[0]):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(up), np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_gssvx_pool_partition_option():
    """Options.pool_partition reaches the executor through the driver."""
    from superlu_dist_tpu.drivers.gssvx import gssvx
    from superlu_dist_tpu.utils.options import Options
    a = poisson2d(10)
    xt = np.random.default_rng(1).standard_normal(a.n_rows)
    b = a.matvec(xt)
    x0, _, _, _ = gssvx(Options(), a, b)
    grid = gridinit(4, 2)
    x1, lu, stats, info = gssvx(Options(pool_partition=True), a, b,
                                grid=grid)
    assert info == 0
    np.testing.assert_allclose(x1, x0, rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_level_granularity_matches_group():
    """granularity="level" (one dispatch per elimination level) must be
    bit-equal to the per-group stream, plain and mesh-sharded."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    plan, avals, thresh = _plan()
    ref = StreamExecutor(plan, "float64")(jnp.asarray(avals),
                                          jnp.asarray(thresh))
    lev = StreamExecutor(plan, "float64", granularity="level")(
        jnp.asarray(avals), jnp.asarray(thresh))
    assert int(lev[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(lev[0], ref[0]):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(rlp))
        np.testing.assert_array_equal(np.asarray(up), np.asarray(rup))
    grid = gridinit(4, 2)
    lev_m = StreamExecutor(plan, "float64", mesh=grid.mesh,
                           granularity="level")(
        jnp.asarray(avals), jnp.asarray(thresh))
    assert int(lev_m[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(lev_m[0], ref[0]):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(up), np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


def test_offload_with_pool_partition():
    """The round-3 config-4 recipe: host-offloaded factor panels + the
    Schur pool sharded across the mesh, together, must match the plain
    stream bit-for-bit."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    plan, avals, thresh = _plan()
    ref = StreamExecutor(plan, "float64")(jnp.asarray(avals),
                                          jnp.asarray(thresh))
    grid = gridinit(4, 2)
    ex = StreamExecutor(plan, "float64", mesh=grid.mesh,
                        pool_partition=True, offload="host")
    assert ex.offload == "host"           # the mode actually engaged
    got = ex(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(got[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(got[0], ref[0]):
        # offload guarantees host-resident results; correctness is the
        # numeric equality below (device-residency internals are covered
        # by the executor's own offload path)
        assert isinstance(lp, np.ndarray)
        np.testing.assert_allclose(lp, np.asarray(rlp),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(up, np.asarray(rup),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.slow
def test_host_share_split_matches_plain():
    """The CPU-share split (SLU_TPU_HOST_FLOPS — the reference's
    gemm_division_cpu_gpu + N_GEMM threshold, SRC/util.c:1271-1360):
    leading small levels run on the host CPU device with one pool handoff.
    On the CPU backend the handoff is same-device, but the full routing /
    handoff / mixed-front finalize path executes and must be bit-equal to
    the unsplit stream, at both granularities."""
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    from superlu_dist_tpu.numeric.stream import _bucket_len
    from superlu_dist_tpu.symbolic.symbfact import _front_flops

    # fine supernodes (no amalgamation) give the real shape: many cheap
    # leaf levels below a few big ancestor levels
    a = poisson2d(16)
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order, relax=4, max_supernode=16,
                            amalg_tol=0.0)
    plan = build_plan(sf)
    avals = sym.data[sf.value_perm]
    thresh = np.sqrt(np.finfo(np.float64).eps) * a.norm_max()

    ref = StreamExecutor(plan, "float64", host_flops=0)(
        jnp.asarray(avals), jnp.asarray(thresh))
    # threshold above the leaf level's cost but below the costliest level,
    # so the split engages AND leaves trailing levels on the device
    lv_cost = {}
    for g in plan.groups:
        fl = _bucket_len(g.batch, 1) * _front_flops(g.w, g.u)
        lv_cost[g.level] = max(lv_cost.get(g.level, 0), fl)
    costs = [lv_cost[lv] for lv in sorted(lv_cost)]
    cut = max(costs)
    assert costs[0] < cut, "plan must have a cheap leaf level"
    for gran in ("group", "level"):
        ex = StreamExecutor(plan, "float64", granularity=gran,
                            host_flops=cut)
        assert ex.host_levels > 0, "threshold must engage on this plan"
        assert ex.host_levels < len({g.level for g in plan.groups}), \
            "split must leave trailing levels on the device"
        out = ex(jnp.asarray(avals), jnp.asarray(thresh))
        assert int(out[1]) == int(ref[1])
        for (lp, up), (rlp, rup) in zip(out[0], ref[0]):
            np.testing.assert_array_equal(np.asarray(lp), np.asarray(rlp))
            np.testing.assert_array_equal(np.asarray(up), np.asarray(rup))
    # host-share combined with offload="host": the lag window must not
    # reach into the host prefix (it would block on host compute and
    # corrupt the comm split); result still bit-equal, all fronts numpy
    exc = StreamExecutor(plan, "float64", offload="host", host_flops=cut)
    assert exc.host_levels > 0
    outc = exc(jnp.asarray(avals), jnp.asarray(thresh))
    assert int(outc[1]) == int(ref[1])
    assert all(isinstance(lp, np.ndarray) for lp, _ in outc[0])
    for (lp, up), (rlp, rup) in zip(outc[0], ref[0]):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(rlp))
        np.testing.assert_array_equal(np.asarray(up), np.asarray(rup))

    # a mesh-sharded executor ignores the host share (everything stays on
    # the mesh)
    grid = gridinit(4, 2)
    exm = StreamExecutor(plan, "float64", mesh=grid.mesh, host_flops=1e7)
    assert exm.host_levels == 0


def test_host_share_step_keeps_jit_when_key_matches_a_device_step():
    """A host-share step whose shape key equals a device step's must not
    run the ahead-of-time executable compiled for the device: that one
    is bound to the device's placement.  Device steps here run on a
    second CPU device, so the host steps' placement differs; the result
    stays bit-equal to the unsplit stream, every device step runs an
    ahead-of-time executable and every host step the jitted kernel."""
    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric.stream import (StreamExecutor, _bucket_len,
                                                 _kernel)
    from superlu_dist_tpu.ops.dense import pivot_kernel
    from superlu_dist_tpu.symbolic.symbfact import _front_flops

    a = poisson3d(6)
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(Options(), a, sym)
    sf = symbolic_factorize(sym, col_order, relax=4, max_supernode=16,
                            amalg_tol=0.0)
    plan = build_plan(sf)
    avals = sym.data[sf.value_perm]
    thresh = np.sqrt(np.finfo(np.float64).eps) * a.norm_max()
    lv_cost = {}
    for g in plan.groups:
        fl = _bucket_len(g.batch, 1) * _front_flops(g.w, g.u)
        lv_cost[g.level] = max(lv_cost.get(g.level, 0), fl)
    # the first threshold whose host prefix shares a key with a later,
    # device-side level
    for cut in sorted(set(lv_cost.values())):
        ex = StreamExecutor(plan, "float64", host_flops=cut + 1)
        if ({k for k, _, _, _, on in ex._steps if on}
                & {k for k, _, _, _, on in ex._steps if not on}):
            break
    with jax.default_device(jax.devices()[1]):
        ref = StreamExecutor(plan, "float64", host_flops=0)(
            jnp.asarray(avals), jnp.asarray(thresh))
        ex = StreamExecutor(plan, "float64", host_flops=cut + 1)
        host = {key for key, _, _, _, on in ex._steps if on}
        dev = {key for key, _, _, _, on in ex._steps if not on}
        assert host & dev, "plan must share a key across the split"
        out = ex(jnp.asarray(avals), jnp.asarray(thresh))
        pivot = pivot_kernel()
        avals_d = jnp.asarray(avals)
        pool = jnp.zeros(plan.pool_size)
        thresh_d = jnp.asarray(thresh)
        cpu0 = jax.devices()[0]
        for key, arrs, child_arrs, _, on_host in ex._steps:
            head = ((jax.device_put(avals_d, cpu0),
                     jax.device_put(pool, cpu0),
                     jax.device_put(thresh_d, cpu0)) if on_host
                    else (avals_d, pool, thresh_d))
            kern = ex._get_kernel(key, pivot, (*head, *arrs, *child_arrs))
            jitted = _kernel(*key, None, False, pivot, ex.gemm_prec)
            assert (kern is jitted) == on_host, (key, on_host)
    assert int(out[1]) == int(ref[1])
    for (lp, up), (rlp, rup) in zip(out[0], ref[0]):
        np.testing.assert_array_equal(np.asarray(lp), np.asarray(rlp))
        np.testing.assert_array_equal(np.asarray(up), np.asarray(rup))

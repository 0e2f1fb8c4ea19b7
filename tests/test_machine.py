"""Machine-parameter and timer sanity probes.

Capability analog of the reference's INSTALL tests (INSTALL/dmachtst.c:
machine epsilon / underflow / overflow probes; INSTALL/timertst.c: timer
resolution), driven by install.csh.  Here they guard the assumptions the
GESP threshold arithmetic makes: thresh = sqrt(eps)·‖A‖ must be
representable and monotone in both working precisions, and the phase
timers must actually resolve the phases they time.
"""

import time

import numpy as np


def _probe_eps(dtype):
    """Smallest e with 1 + e != 1 — must match np.finfo."""
    one = dtype(1.0)
    e = dtype(1.0)
    while one + e / dtype(2.0) != one:
        e = e / dtype(2.0)
    return e


def test_machine_epsilon_f64():
    assert _probe_eps(np.float64) == np.finfo(np.float64).eps


def test_machine_epsilon_f32():
    assert _probe_eps(np.float32) == np.finfo(np.float32).eps


def test_underflow_overflow_bounds():
    for dt in (np.float32, np.float64):
        fi = np.finfo(dt)
        assert fi.tiny > 0 and np.isfinite(fi.tiny)
        assert np.isfinite(fi.max)
        with np.errstate(over="ignore"):
            assert np.isinf(dt(fi.max) * dt(2.0))
        # GESP threshold must stay representable across the anorm range
        for anorm in (fi.tiny, 1.0, fi.max ** 0.5):
            t = np.sqrt(fi.eps) * dt(anorm)
            assert np.isfinite(t) and t >= 0


def test_timer_resolution():
    """perf_counter must resolve well under one solver phase (~ms)."""
    res = time.get_clock_info("perf_counter").resolution
    assert res < 1e-4
    t0 = time.perf_counter()
    while time.perf_counter() == t0:
        pass
    assert time.perf_counter() - t0 < 1e-3


def test_stats_timer_accumulates():
    from superlu_dist_tpu.utils.stats import Stats
    s = Stats()
    with s.timer("FACT"):
        time.sleep(0.01)
    assert s.utime["FACT"] >= 0.009


# ---- compile-cache policy (utils/jaxcache.py) ----------------------------

def test_compile_cache_env_dir_is_the_only_cache(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache:
    enable_compile_cache configures no other, even when handed one."""
    import jax

    import superlu_dist_tpu.utils.jaxcache as jc

    env_dir = str(tmp_path / "from-env")
    prior = (jc.current_cache_dir(), jc.cache_enabled())
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_dir", env_dir)
    try:
        assert jc.cache_dir() == env_dir
        assert jc.enable_compile_cache() == env_dir
        assert jc.enable_compile_cache(str(tmp_path / "other")) == env_dir
        assert jc.current_cache_dir() == env_dir
        assert not (tmp_path / "other").exists()
        assert jc.bucket_warm_marker("d").startswith(env_dir)
    finally:
        jax.config.update("jax_compilation_cache_dir", prior[0])
        jax.config.update("jax_enable_compilation_cache", prior[1])


def test_compile_cache_defaults_to_checkout_dir(monkeypatch):
    """Without the variable, the cache is the fixed .cache/jax inside
    the checkout — no machine-scoped or re-scoped subdirectory."""
    import os

    import jax

    import superlu_dist_tpu.utils.jaxcache as jc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".cache", "jax")
    prior = (jc.current_cache_dir(), jc.cache_enabled())
    try:
        assert jc.cache_dir() == want
        assert jc.enable_compile_cache() == want
        assert jc.current_cache_dir() == want
        assert jc.cache_enabled()
    finally:
        jax.config.update("jax_compilation_cache_dir", prior[0])
        jax.config.update("jax_enable_compilation_cache", prior[1])


def test_dryrun_throwaway_cache_never_outlives_its_directory(monkeypatch):
    """dryrun_multichip runs with the persistent compile cache OFF
    (XLA:CPU entries of collective programs wedge on reload) and hands
    the caller's setting back unchanged afterwards, on or off."""
    import importlib.util
    import os

    import jax

    import superlu_dist_tpu.utils.jaxcache as jc

    path = os.path.join(os.path.dirname(__file__), "..",
                        "__graft_entry__.py")
    spec = importlib.util.spec_from_file_location("__graft_entry__", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    seen = []
    # the cache policy is what's under test, not the dryrun body
    monkeypatch.setattr(mod, "_dryrun_body",
                        lambda n: seen.append(jc.cache_enabled()))

    prior_dir, prior_on = jc.current_cache_dir(), jc.cache_enabled()
    try:
        for caller_on in (True, False):
            jax.config.update("jax_enable_compilation_cache", caller_on)
            mod.dryrun_multichip(2)
            assert jc.cache_enabled() is caller_on
            assert jc.current_cache_dir() == prior_dir
        assert seen == [False, False]
    finally:
        jax.config.update("jax_enable_compilation_cache", prior_on)

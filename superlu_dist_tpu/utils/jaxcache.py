"""Persistent XLA compile-cache policy, in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax keeps its persistent
cache in that directory and this module configures no other.  Otherwise
the cache is ``.cache/jax`` inside the checkout (gitignored): a fixed
path, because the directory is part of what a later process must find
again, so a directory that moves never hits.

Warm markers (scripts/warm_compile_cache.py) live inside the cache
directory they vouch for, under ``.warm/``.
"""

import os

#: the environment variable jax itself reads for its cache directory
ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def cache_dir() -> str:
    """The persistent cache directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the checkout's fixed ``.cache/jax``."""
    return os.environ.get(ENV_DIR) or os.path.join(_repo_root(), ".cache",
                                                   "jax")


def bucket_warm_marker(digest: str) -> str:
    """Warm-cache marker path for one CLOSED bucket set (the mega
    executor's compiled-program identity, FactorPlan.bucket_set_digest).
    The marker vouches that every program of that set is resident in
    the cache directory it lives in, so a serving fleet (or a
    persist.from_bundle cold start) whose plans map onto the same
    buckets compiles nothing."""
    return os.path.join(cache_dir(), ".warm", f"bucketset.{digest}")


def bucket_set_warm(digest: str) -> bool:
    """True when scripts/warm_compile_cache.py (or a completed mega
    prebake) has marked this bucket set's programs resident."""
    return os.path.exists(bucket_warm_marker(digest))


def mark_bucket_set_warm(digest: str) -> str:
    """Record a prebaked bucket set (never raises — markers are an
    optimization, exactly like the cache they vouch for)."""
    path = bucket_warm_marker(digest)
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        open(path, "a").close()
    except OSError:
        pass
    return path


def enable_compile_cache(path: str | None = None) -> str:
    """Turn the persistent compile cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is the cache
    and ``path`` is ignored; otherwise ``path`` (default: the checkout's
    ``.cache/jax``).  Every entry is cached regardless of size or
    compile time.  Only subsequent compiles are affected."""
    import jax
    if os.environ.get(ENV_DIR):
        path = os.environ[ENV_DIR]
    else:
        path = path or cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # compile-census boundary (obs/compilestats.py): build records can
    # now attribute disk hit/miss by entry-count delta in this dir
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    COMPILE_STATS.note_cache_dir(path)
    return path


def disable_compile_cache() -> None:
    """Turn the persistent compile cache OFF (jax compiles in memory
    only) without touching which directory is configured."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


def cache_enabled() -> bool:
    """Whether jax's persistent compile cache is currently on."""
    import jax
    return bool(jax.config.jax_enable_compilation_cache)


def current_cache_dir() -> str | None:
    """The cache dir jax is currently configured with (None if unset)."""
    import jax
    return jax.config.jax_compilation_cache_dir

"""Compile census — per-shape-key accounting of every jit build.

The n=110592 TPU factor died inside ``factor-compile`` after the 1350 s
watchdog (BENCH_r02: 119 kernels / 455 groups) and left no artifact
saying WHICH shape-key buckets ate the time.  This registry is that
artifact's source: every jit build site (``numeric/stream.py`` kernel
factories, the fused ``make_factor_fn`` program, ``solve/device.py``
sweep kernels) records one :class:`CompileRecord` per build — site,
bucket key, build seconds, arg count, and whether the persistent
XLA compile cache (``utils/jaxcache.py``) satisfied it from disk.

Measurement model: a build is the first call of a new jitted program
(``jax.jit`` traces, lowers and compiles synchronously inside it, then
issues the call asynchronously) or an ahead-of-time ``lower().compile()``.
Each build site runs it inside :meth:`CompileStats.build`, which times
it, records the census entry and opens the ``compile`` span
(``slu.compile.<name>`` on the profiler's clock, ``name`` from
:data:`SPAN_NAMES`), so a census record and a build span are one event.
The recorded ``seconds`` include trace+lower+compile plus the (async)
issue, which compile dominates by orders of magnitude on any build that
matters.  ``scripts/compile_census.py --live`` provides the exact
trace/lower/compile stage split offline, where double work is
acceptable; records carry the split when a caller measured it.

Persistent-cache attribution: JAX's own events seen on the building
thread during the build — ``/jax/compilation_cache/cache_misses``
(XLA compiled and wrote: not a hit) or ``cache_hits`` (loaded from
disk: a hit).  Without either (no persistent cache) the flag is None.

Consumers: the ``compile`` trace category
(obs/trace.py), the ``stats.compile`` block in the PStatPrint-analog
report (utils/stats.py via drivers/gssvx.factorize_numeric), the
``compile_seconds`` / ``compile_census`` fields of the bench JSON row,
flight-recorder postmortems (obs/flightrec.py), and
``scripts/compile_census.py``.  :func:`tally` counts the program calls
one thread makes through :func:`call` as hits (a built program reused)
and misses (a build): the ``device-solve`` span's ``program_hits`` /
``program_misses``.
"""

from __future__ import annotations

import contextlib
import threading
import time
import weakref
from dataclasses import dataclass

from superlu_dist_tpu.utils.lockwatch import make_lock

#: The ``compile`` span name (``slu.compile.<name>``) of each census site;
#: a site not listed is its own name (``solve``, ``diag_inv``, ``spmv``).
SPAN_NAMES = {"stream._kernel": "stream", "stream._level_fn": "stream",
              "make_factor_fn": "fused", "mega._kernel": "mega",
              "spmd.factor": "spmd"}

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
#: JAX's persistent-cache events, counted per thread (a build and its
#: cache lookup run on one thread; parallel builds run on several)
_events = threading.local()
_listening = []
_listen_lock = make_lock("compilestats._listen_lock")


def _on_event(name, **_):
    if name == _HIT:
        _events.hits = getattr(_events, "hits", 0) + 1
    elif name == _MISS:
        _events.misses = getattr(_events, "misses", 0) + 1


def _cache_events() -> tuple:
    """(hits, misses) seen on this thread so far."""
    if not _listening:
        import jax
        with _listen_lock:
            if not _listening:
                jax.monitoring.register_event_listener(_on_event)
                _listening.append(True)
    return getattr(_events, "hits", 0), getattr(_events, "misses", 0)


class Build:
    """What a build site may add to its record: the exact stage split,
    when it staged the build explicitly."""

    __slots__ = ("trace_seconds", "lower_seconds", "compile_seconds")

    def __init__(self):
        self.trace_seconds = self.lower_seconds = None
        self.compile_seconds = None


@dataclass
class CompileRecord:
    """One jit build: where, what bucket, how long, and whether the
    persistent compile cache served it from disk."""

    site: str                 # build site, e.g. "stream._kernel"
    key: str                  # bucket key, e.g. "lu b16 m32 w16 u16"
    seconds: float            # first-invocation wall time (see module doc)
    t0: float = 0.0           # time.perf_counter() at build start
    n_args: int = 0           # kernel parameter count
    builds: int = 1           # jit programs built inside this record
    persistent_hit: bool | None = None   # disk-cache hit (None: no cache)
    trace_seconds: float | None = None   # exact stage split when the
    lower_seconds: float | None = None   # caller staged explicitly
    compile_seconds: float | None = None # (scripts/compile_census.py)


class CompileStats:
    """Process-wide compile census (module singleton ``COMPILE_STATS``).

    ``marker()`` + ``block(since=...)`` let callers account a window
    (bench's factor-compile phase, one factorize_numeric call) without
    resetting global state.
    """

    def __init__(self):
        self._lock = make_lock("CompileStats._lock")
        self.records: list[CompileRecord] = []
        self._cache_dir: str | None = None
        # pending-key accounting: executors announce their FULL expected
        # kernel set at construction (they know it from the plan), and
        # build() retires keys as they build — so a watchdog firing
        # mid-compile can name the shape keys still UNCOMPILED (the
        # BENCH_r02 postmortem gap: "died in factor-compile, 119
        # kernels" with no record of which were left)
        self._announced: set = set()
        self._built: set = set()
        # program-audit notes (utils/programaudit.py, SLU_TPU_VERIFY_
        # PROGRAMS=1): per-(site, label) donation-coverage and
        # baked-const-bytes stats — empty dict when auditing never ran
        self._audits: dict = {}

    # ---- persistent-cache boundary (utils/jaxcache.py) -----------------
    def note_cache_dir(self, path: str | None) -> None:
        """jaxcache.enable_compile_cache announces the active persistent
        cache directory (reported in :meth:`block`)."""
        with self._lock:
            self._cache_dir = path

    # ---- recording -----------------------------------------------------
    @contextlib.contextmanager
    def build(self, site: str, key: str, n_args: int = 0,
              before: float = 0.0):
        """Run one program build inside the body: time it, read JAX's
        persistent-cache events on this thread, open the ``compile`` span
        and, when the body returns, record the census entry.  ``before``
        is build work the site did ahead of the body (an AOT build's
        lowering, on another thread) and counts into ``seconds``.  Yields
        a :class:`Build` for an explicit stage split."""
        from superlu_dist_tpu.obs.trace import get_tracer
        hits0, misses0 = _cache_events()
        t0 = time.perf_counter()
        b = Build()
        with get_tracer().span(SPAN_NAMES.get(site, site), "compile",
                               site=site, key=key,
                               n_args=int(n_args)) as sp:
            yield b
            hits, misses = _cache_events()
            hit = (False if misses > misses0
                   else True if hits > hits0 else None)
            sp.set(persistent_hit=hit)
        rec = CompileRecord(
            site=site, key=key, t0=t0 - before,
            seconds=before + time.perf_counter() - t0, n_args=int(n_args),
            persistent_hit=hit,
            trace_seconds=b.trace_seconds, lower_seconds=b.lower_seconds,
            compile_seconds=b.compile_seconds)
        with self._lock:
            self.records.append(rec)
            self._built.add((site, key))
            self._announced.discard((site, key))

    # ---- pending-key accounting ----------------------------------------
    def announce(self, site: str, keys) -> None:
        """An executor declares the kernel keys it EXPECTS to build
        (before any of them compile).  Keys this process already built
        are not re-announced — a warmed executor re-running the same
        plan leaves nothing pending."""
        with self._lock:
            for key in keys:
                if (site, key) not in self._built:
                    self._announced.add((site, str(key)))

    def pending(self) -> list[dict]:
        """Announced-but-unbuilt kernel keys, sorted — the census delta
        a factor-compile watchdog row emits so the postmortem names the
        offending buckets (bench.py `pending_kernels`)."""
        with self._lock:
            return [{"site": s, "key": k}
                    for s, k in sorted(self._announced)]

    # ---- program-audit notes (slulint v4 runtime twin) -----------------
    def audit_note(self, site: str, key: str, stats: dict) -> None:
        """The program auditor reports one audited program's stats
        (donation coverage %, baked const bytes, finding count)."""
        with self._lock:
            self._audits[(site, key)] = dict(stats)

    def audit_block(self) -> dict:
        """Aggregate program-audit stats for the stats.compile block and
        the bench row: program count, donated/dead byte totals, overall
        donation coverage %, total baked-const bytes, plus the v6
        sharding-twin aggregates (programs_sharding_audited,
        peak_bytes_est = the worst program's static high-water mark,
        replicated_bytes = gathered/replicated traffic across all
        audited programs)."""
        with self._lock:
            audits = [dict(v) for v in self._audits.values()]
            sharding = [dict(v) for (s, k), v in self._audits.items()
                        if k.endswith("#sharding")]
        donated = sum(a.get("donated_bytes", 0) for a in audits)
        dead = sum(a.get("dead_bytes", 0) for a in audits)
        return {
            "programs": len(audits),
            "findings": sum(a.get("findings", 0) for a in audits),
            "donated_bytes": int(donated),
            "dead_bytes": int(dead),
            "donation_coverage_pct": (
                100.0 if dead == 0
                else round(100.0 * donated / dead, 2)),
            "baked_const_bytes": sum(a.get("baked_const_bytes", 0)
                                     for a in audits),
            "programs_sharding_audited": len(sharding),
            "peak_bytes_est": max(
                (a.get("peak_bytes_est", 0) for a in sharding),
                default=0),
            "replicated_bytes": sum(a.get("replicated_bytes", 0)
                                    for a in sharding),
        }

    # ---- querying ------------------------------------------------------
    # Export-path readers snapshot under the lock: a SolveServer
    # dispatcher (or scrubber postmortem) records builds concurrently
    # with a census/flightrec export, and an unlocked slice racing
    # build()/_reset() tears the window (slulint SLU108's discipline,
    # applied to this singleton by hand — it spawns no thread itself).
    def _snap(self, since: int = 0) -> list:
        with self._lock:
            return list(self.records[since:])

    def marker(self) -> int:
        """Opaque position marker for windowed accounting."""
        with self._lock:
            return len(self.records)

    def total_seconds(self, since: int = 0) -> float:
        return float(sum(r.seconds for r in self._snap(since)))

    def census(self, since: int = 0) -> list[dict]:
        """Per-(site, key) aggregation of the records after ``since``,
        sorted by total seconds descending — the "which buckets dominate
        cold-compile" table.  Rows carry ``peak_bytes_est`` (the SLU121
        static high-water estimate) when the sharding twin audited the
        matching program (``key#sharding`` audit note)."""
        agg: dict[tuple, dict] = {}
        for r in self._snap(since):
            row = agg.get((r.site, r.key))
            if row is None:
                row = agg[(r.site, r.key)] = {
                    "site": r.site, "key": r.key, "n": 0, "builds": 0,
                    "seconds": 0.0, "persistent_hits": 0, "n_args": r.n_args}
            row["n"] += 1
            row["builds"] += r.builds
            row["seconds"] += r.seconds
            row["persistent_hits"] += 1 if r.persistent_hit else 0
        with self._lock:
            peaks = {(s, k[:-len("#sharding")]): v.get("peak_bytes_est")
                     for (s, k), v in self._audits.items()
                     if k.endswith("#sharding")}
        out = sorted(agg.values(), key=lambda row: -row["seconds"])
        for row in out:
            row["seconds"] = round(row["seconds"], 4)
            peak = peaks.get((row["site"], row["key"]))
            if peak is not None:
                row["peak_bytes_est"] = int(peak)
        return out

    def block(self, since: int = 0, top: int = 8) -> dict:
        """The ``stats.compile`` block: totals plus the top buckets.

        ``fresh_seconds`` counts only builds the persistent cache did
        NOT serve from disk — the time spent actually COMPILING, which
        a bucket-set-keyed warm start drives to ~0 (``seconds`` keeps
        the first-invocation total: trace + lower + cache load)."""
        recs = self._snap(since)
        audit = self.audit_block()
        return {
            "program_audit": audit if audit["programs"] else None,
            "builds": sum(r.builds for r in recs),
            "seconds": round(sum(r.seconds for r in recs), 4),
            "fresh_seconds": round(sum(r.seconds for r in recs
                                       if not r.persistent_hit), 4),
            "persistent_hits": sum(1 for r in recs if r.persistent_hit),
            "cache_dir": self._cache_dir,
            "census": self.census(since)[:top],
        }

    def _reset(self) -> None:
        """Test hygiene: drop all records and pending announcements (the
        cache-dir note and the built-key set survive — they are
        process-wide facts, like the executors' kernel caches)."""
        with self._lock:
            self.records = []
            self._announced = set()
            self._audits = {}


COMPILE_STATS = CompileStats()


#: per jitted program, the argument signatures it has been called with
_CALLED = weakref.WeakKeyDictionary()
_called_lock = make_lock("compilestats._called_lock")


def _signature(args) -> tuple:
    import jax
    return tuple((getattr(x, "shape", None), str(getattr(x, "dtype", "")))
                 for x in jax.tree_util.tree_leaves(args))


class Tally:
    """Program calls counted by :func:`tally`: ``hits`` reused a built
    program, ``misses`` built one."""

    __slots__ = ("hits", "misses")

    def __init__(self):
        self.hits = self.misses = 0


#: the innermost open :func:`tally` of each thread
_tallies = threading.local()


@contextlib.contextmanager
def tally():
    """Count the :func:`call` invocations this thread makes in the
    body."""
    t, prev = Tally(), getattr(_tallies, "open", None)
    _tallies.open = t
    try:
        yield t
    finally:
        _tallies.open = prev


def call(site: str, key: str, fn, *args):
    """Call the jitted ``fn``; its first call with an argument signature
    builds the program and runs inside :meth:`CompileStats.build`.  Holds
    no reference that keeps ``fn`` alive."""
    sig = _signature(args)
    with _called_lock:
        seen = sig in _CALLED.get(fn, ())
    t = getattr(_tallies, "open", None)
    if seen:
        if t is not None:
            t.hits += 1
        return fn(*args)
    if t is not None:
        t.misses += 1
    with COMPILE_STATS.build(site, key, n_args=len(sig)):
        out = fn(*args)
    with _called_lock:
        _CALLED.setdefault(fn, set()).add(sig)
    return out

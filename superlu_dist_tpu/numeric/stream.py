"""Streamed factorization executor — per-bucket kernels, async dispatch.

The whole-program jit (factor.make_factor_fn) is ideal for moderate plans,
but its HLO grows with the number of (level, bucket) groups; large matrices
produce programs that compile slowly (a whole-factor program at
n=110,592 did not finish compiling for a v5e within two minutes).  This
executor instead compiles ONE small kernel per distinct shape key and
*streams* the groups through it in level order, keeping the Schur pool
resident on the device and chaining all dispatches asynchronously (the
role of the reference's pipelined look-ahead + cuBLAS streams,
SRC/pdgstrf.c:1100-1348, dSchCompUdt-cuda.c:123-251).  The kernels are
compiled ahead of the stream, in parallel (``_build_kernels``).

Shape keys repeat because every host-built index array is padded to a
power-of-2 bucket: out-of-range scatter indices are dropped (mode='drop')
and gathers fill zeros (mode='fill'), so padding entries are no-ops.
Padded batch slots become identity fronts (ws == 0 pads the whole pivot
diagonal; LU of I = I, no tiny pivots).  Compile count is O(#distinct
keys), not O(#groups).
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp

from superlu_dist_tpu.numeric.plan import FactorPlan
from superlu_dist_tpu.numeric.factor import group_step
from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
from superlu_dist_tpu.obs.metrics import get_metrics
from superlu_dist_tpu.obs.trace import NULL_TRACER, get_tracer
from superlu_dist_tpu.symbolic.symbfact import _front_flops
from superlu_dist_tpu.utils.lockwatch import make_lock
from superlu_dist_tpu.utils.options import env_float, env_int

#: Shape keys whose first (compiling) invocation the compile census has
#: already accounted — process-wide, mirroring the lru cache on _kernel.
_CENSUSED_KEYS = set()

#: Ahead-of-time compiled kernels, by (jitted kernel, call signature) —
#: process-wide like the lru cache on _kernel (StreamExecutor.
#: _build_kernels fills it).  The signature (shape, dtype and placement
#: of every argument, ``_signature``) is what a compiled executable is
#: bound to, so a call it does not fit — another value count, a
#: host-share step committed to the CPU — misses and takes the jit path.
_COMPILED = {}

#: Host memory one concurrent kernel compile may take: the 89 kernels of
#: the n=110,592 plan, compiled for a v5e on 8 threads, peaked at 8 GB.
BUILD_BYTES = 3 << 29


def _signature(args) -> tuple:
    """The abstract signature of a call: shape, dtype and sharding of
    every argument."""
    return tuple((x.shape, x.dtype, x.sharding) for x in args)


def build_workers(n: int) -> int:
    """Threads for compiling ``n`` programs at once: one per core, and
    no more than half the host's physical memory holds at BUILD_BYTES
    each.  One on a TPU whose compiler did not get the package's fiber
    stack flag (superlu_dist_tpu/__init__.py)."""
    import superlu_dist_tpu
    if (not superlu_dist_tpu.TPU_PARALLEL_COMPILE
            and jax.default_backend() == "tpu"):
        return 1
    mem = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    return max(1, min(n, os.cpu_count() or 1, mem // 2 // BUILD_BYTES))


def compile_all(lowered: list, progress: bool = False, label=str,
                build=None):
    """Compile ``lowered`` (a list of jax ``Lowered``) in parallel threads
    — XLA compiles outside the GIL.  ``build(i)``, when given, is the
    context manager each compile runs inside, on its thread (the compile
    census's build span).  Yields (index, executable, seconds) in input
    order."""
    workers = build_workers(len(lowered))
    if progress:
        print(f"[build] {len(lowered)} programs on {workers} threads",
              file=sys.stderr, flush=True)

    def one(i):
        t0 = time.perf_counter()
        with build(i) if build is not None else contextlib.nullcontext():
            exe = lowered[i].compile()
        return exe, time.perf_counter() - t0

    with ThreadPoolExecutor(workers) as ex:
        for i, (exe, secs) in enumerate(ex.map(one, range(len(lowered)))):
            if progress:
                print(f"[build] {i + 1}/{len(lowered)} {label(i)} in "
                      f"{secs:.1f}s", file=sys.stderr, flush=True)
            yield i, exe, secs


# Look-ahead window (the num_lookaheads analog, reference
# SRC/pdgstrf.c:624-697 + sp_ienv case 4).  The reference needs a
# dependency table + look-ahead pipeline because panels wait on MPI
# messages between ranks; here dispatch is async and every kernel is
# serialized on the donated Schur pool, so the only look-ahead that
# matters is how many groups of FACTORED PANELS may stay in flight
# device-side before their D2H offload is forced to complete — deeper =
# more compute/transfer overlap, shallower = less HBM held by panels.
# Env SLU_TPU_OFFLOAD_LAG (default 8), latched per StreamExecutor.


class RetraceSentinel:
    """Runtime recompile watchdog — the dynamic counterpart of slulint's
    SLU105 cache-key rule (part of the SLU106 runtime tier).

    The streamed executor's compile count is bounded by distinct shape
    keys, all built on the FIRST call; a warmed executor re-running the
    same plan must build ZERO new kernels.  Any rebuild after warmup
    means a cache-key input changed mid-run — an env knob
    (SLU_TPU_PIVOT_KERNEL), a mesh identity, a dtype — which is exactly
    the silent recompile axis SLU105 polices statically.  Rebuilds are
    counted process-wide, reported to stderr, surfaced as a `verify`
    trace span, and accumulated into Stats.retraces by the driver
    (drivers/gssvx.factorize_numeric)."""

    def __init__(self):
        self.total = 0            # unexpected rebuilds, process-wide
        self.events = []          # (factory, builds), bounded window
        # module-global sentinel, bumped from whichever thread ran the
        # executor (a SolveServer dispatcher, a user thread, the
        # scrubber's re-serve) — totals must not tear across them
        self._lock = make_lock("stream.RetraceSentinel._lock")

    def record(self, factory: str, builds: int, tracer=None) -> None:
        with self._lock:
            self.total += builds
            self.events = (self.events + [(factory, int(builds))])[-32:]
        print(f"[SLU106] retrace sentinel: {builds} unexpected jit kernel "
              f"build(s) in {factory} after warmup — a cache-key input "
              "(env knob, mesh identity, dtype) changed mid-run; a warmed "
              "executor expects 0 recompiles", file=sys.stderr, flush=True)
        if tracer is not None and tracer.enabled:
            tracer.complete("retrace-sentinel", "verify",
                            time.perf_counter(), 0.0,
                            factory=factory, builds=int(builds))
        m = get_metrics()
        if m.enabled:
            m.inc("slu_retraces_total", float(builds), factory=factory)


RETRACE_SENTINEL = RetraceSentinel()


def _bucket_len(n: int, lo: int = 8, base: float = 2.0) -> int:
    """Next rung of the canonical bucket ladder (plan.bucket_rung — the
    ONE ladder shared with the plan's front bucketing, so schedule
    alignment and kernel caching can never disagree about what "the same
    shape" means).  The defaults reproduce the historical pow-2 rounding;
    base=4 for index arrays whose padding costs only a cheap gather:
    coarser rungs collapse more compile keys."""
    from superlu_dist_tpu.numeric.plan import bucket_rung
    return bucket_rung(max(int(n), 1), lo=lo, growth=base)


def _pad_to(arr: np.ndarray, length: int, fill) -> np.ndarray:
    out = np.full(length, fill, dtype=np.int64)
    out[:len(arr)] = arr
    return out


@functools.lru_cache(maxsize=None)
def _kernel(dims, l_a, child_shapes, pool_size, dtype, mesh,
            pool_partition, pivot, gemm_prec="highest"):
    """Jitted group step for one shape key (optionally mesh-sharded).

    With a mesh, the dense factor math shards batch-over-"snode" and
    columns-over-"panel" exactly like the fused executor (make_factor_fn);
    the irregular gathers/scatters stay replicated (see factor.py notes on
    the SPMD partitioner).  This is the VERDICT-r1 gap #3: the real-TPU
    executor must be shardable where the fused whole-program jit won't
    compile.  pool_partition shards the 1-D Schur pool across all mesh
    devices (see make_factor_fn) — per-chip pool memory divides by the
    device count.
    """
    front_sharding = pivot_sharding = replicated = pool_sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from superlu_dist_tpu.numeric.factor import pool_spec
        front_sharding = NamedSharding(mesh, P("snode", None, "panel"))
        pivot_sharding = NamedSharding(mesh, P("snode", None, None))
        replicated = NamedSharding(mesh, P(None, None))
        pool_sharding = pool_spec(mesh, pool_partition)

    def step(avals, pool, thresh, a_slot, a_flat, a_src, ws, off, *child_arr):
        if pool_sharding is not None:
            pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
        children = [(ub, child_arr[3 * i], child_arr[3 * i + 1],
                     child_arr[3 * i + 2])
                    for i, (ub, _) in enumerate(child_shapes)]
        out, pool, tiny = group_step(dims, avals, pool, thresh,
                                     a_slot, a_flat, a_src, ws, off, children,
                                     front_sharding=front_sharding,
                                     pivot_sharding=pivot_sharding,
                                     replicated=replicated, pivot=pivot,
                                     gemm_prec=gemm_prec)
        if pool_sharding is not None:
            pool = jax.lax.with_sharding_constraint(pool, pool_sharding)
        return out, pool, tiny

    # the program's name carries its front shape, so a device trace's
    # ops say which shape key they belong to
    step.__name__ = "factor_b{}_m{}_w{}_u{}".format(*dims)
    # pool is threaded linearly through the group stream — donating it lets
    # XLA scatter in place instead of copying pool_size entries per group
    return jax.jit(step, donate_argnums=(1,))


class StreamExecutor:
    """Callable factorization: (avals, thresh) -> (fronts, tiny_count).

    Reusable across refactorizations with the same plan (SamePattern tier).
    """

    def __init__(self, plan: FactorPlan, dtype="float64", mesh=None,
                 offload: str = "auto", pool_partition: bool = False,
                 granularity: str = "group", host_flops=None,
                 gemm_prec=None):
        """offload: "none" keeps every factored panel on the device;
        "host" streams each group's (lpanel, upanel) to host memory as
        soon as it is produced (copy_to_host_async overlaps the next
        groups' compute), so device memory holds only the Schur pool plus
        the in-flight group — the factor-size wall that limits single-chip
        problem size (a 16 GB v5e holds ~n=50k padded f32 factors;
        streaming lifts that to host-RAM scale, the same reason the
        reference's GPU path keeps factors in host memory and ships only
        panels to the accelerator, dSchCompUdt-cuda.c:194-241).
        "auto" offloads iff the padded factor bytes exceed
        SLU_TPU_FRONT_BYTES_LIMIT (default 6e9) on an accelerator backend.
        """
        plan.check_index_width()
        self.plan = plan
        self.dtype = str(jnp.dtype(dtype))
        self.mesh = mesh
        self.pool_partition = bool(pool_partition and mesh is not None)
        # GEMM-precision tier, resolved in THIS uncached constructor and
        # latched for the executor's lifetime (it is part of
        # get_executor's cache key, so a changed knob yields a fresh
        # executor — slulint SLU105)
        from superlu_dist_tpu.ops.dense import (gemm_precision,
                                                resolve_gemm_tier)
        self.gemm_prec = gemm_precision(gemm_prec)
        # the tier the arithmetic will actually RUN for this dtype
        # (bf16 degrades to default on complex) — kernel spans report
        # THIS, never a tier the math didn't use (slulint v5 satellite)
        self.gemm_prec_resolved = resolve_gemm_tier(self.gemm_prec,
                                                    self.dtype)
        # granularity="level" traces all bucket groups sharing one
        # schedule wave (Group.level: the elimination level under
        # SLU_TPU_SCHEDULE=level, the monotone dispatch wave under the
        # dataflow scheduler — consecutive either way) into ONE jitted
        # program; group_step calls thread the pool sequentially, so
        # intra-wave dependencies the dataflow packer allows are still
        # honored.  Dispatch count drops from #groups to #waves, at the
        # cost of per-wave (mostly unique) compiles.  "group" keeps the
        # bounded compile count of one kernel per distinct shape key.
        if granularity not in ("group", "level"):
            raise ValueError(f"granularity must be 'group' or 'level', "
                             f"got {granularity!r}")
        self.granularity = granularity
        self._level_fns = {}
        if offload == "auto":
            limit = env_float("SLU_TPU_FRONT_BYTES_LIMIT")
            itemsize = jnp.dtype(dtype).itemsize
            padded = sum(
                _bucket_len(g.batch, 1) * (g.m * g.w + g.w * g.u)
                for g in plan.groups) * itemsize
            offload = ("host" if padded > limit
                       and jax.default_backend() != "cpu" else "none")
        self.offload = offload
        self.last_dispatch_seconds = None   # async-issue time of last call
        # time blocked materializing offloaded panels (D2H waits inside
        # the dispatch loop) — with last_dispatch_seconds this is the
        # PROFlevel comm-split analog (pdgstrf.c:1930-1951): issue /
        # transfer-wait / (the rest =) device compute
        self.last_offload_wait_seconds = None
        self._lag = env_int("SLU_TPU_OFFLOAD_LAG")
        self._tracer = NULL_TRACER   # latched from the global per call
        # non-finite sentinel (set per call by numeric_factorize): when
        # armed, every group materialized on the host mid-stream is
        # isfinite-checked so a breakdown aborts the stream at the
        # offending supernode instead of NaN-ing the remaining levels
        self.check_finite = False
        # crash-consistency hooks (set per call by numeric_factorize,
        # docs/RELIABILITY.md): a persist.checkpoint.FactorCheckpointer
        # noting every completed group, a persist.checkpoint.ResumeState
        # splicing a durable frontier in (consumed one-shot), a
        # utils.deadline.Deadline polled between dispatch groups, and a
        # testing.chaos.ChaosMonkey injector — all None on the
        # production fast path (one `is None` test per group each)
        self.checkpoint = None
        self.resume = None
        self.deadline = None
        self.chaos = None
        # retrace sentinel state (see RetraceSentinel): first call warms
        # the kernel caches; later calls must build nothing new
        self._warmed = False
        self.last_kernel_builds = 0
        self.last_retraces = 0

        # Host-share split (the reference's CPU/GPU work division:
        # gemm_division_cpu_gpu + the N_GEMM flops threshold,
        # SRC/util.c:1271-1360, sp_ienv case 7).  Leading elimination
        # levels whose every group executes fewer than `host_flops` flops
        # run on the host CPU backend — they are dispatch-latency-bound on
        # the accelerator (thousands of tiny leaf LUs cost more in kernel
        # launch than in math) — with ONE pool handoff to the device
        # where the large fronts begin.  Disabled by default
        # (host_flops=0); env SLU_TPU_HOST_FLOPS overrides.  Mesh-sharded
        # runs keep everything on the mesh.
        if host_flops is None:
            host_flops = env_float("SLU_TPU_HOST_FLOPS")
        self._host_levels = set()
        self._cpu_dev = None
        if host_flops > 0 and mesh is None:
            try:
                self._cpu_dev = jax.devices("cpu")[0]
            except RuntimeError:
                self._cpu_dev = None
        if self._cpu_dev is not None:
            lv_max = {}
            for g in plan.groups:
                fl = _bucket_len(g.batch, 1) * _front_flops(g.w, g.u)
                lv_max[g.level] = max(lv_max.get(g.level, 0.0), fl)
            for lv in sorted(lv_max):
                if lv_max[lv] < host_flops:
                    self._host_levels.add(lv)
                else:
                    break
        self.host_levels = len(self._host_levels)
        self._n_host_groups = sum(1 for g in plan.groups
                                  if g.level in self._host_levels)

        # executor-resident lengths the call loop reads (the mega
        # subclass pads both to canonical ladder rungs so its programs
        # are matrix-size-independent)
        self._pool_len = plan.pool_size
        self._steps = self._build_steps()
        self._announce_keys()

    def _build_steps(self) -> list:
        """Per-group (key, assembly arrays, child arrays, batch, on_host)
        tuples in dispatch order.  Overridden by the mega executor
        (numeric/mega.py), which packs the same metadata onto
        per-bucket-canonical shapes instead of per-group ones."""
        plan = self.plan
        n_avals = len(plan.pattern_indices)
        steps = []
        for grp in plan.groups:
            on_host = grp.level in self._host_levels
            # host-group index arrays go straight numpy -> cpu device (a
            # jnp.asarray first would bounce them through the accelerator)
            _put = ((lambda x: jax.device_put(x, self._cpu_dev))
                    if on_host else jnp.asarray)
            b = _bucket_len(grp.batch, 1)
            la = _bucket_len(len(grp.a_src), lo=64, base=4.0)
            # batch padding: slot b-? -> identity fronts via ws=0; scatter
            # slots == b are dropped; gather sources past end fill 0
            a = (_pad_to(grp.a_slot, la, b), _pad_to(grp.a_flat, la, 0),
                 _pad_to(grp.a_src, la, n_avals),
                 _pad_to(grp.ws, b, 0), _pad_to(grp.off, b, plan.pool_size))
            child_arrs = []
            child_shapes = []
            for cs in grp.children:
                c = _bucket_len(len(cs.child_off), 1, base=4.0)
                rel = np.full((c, cs.ub), grp.m, dtype=np.int64)
                rel[:len(cs.rel)] = cs.rel
                child_arrs.extend([
                    _put(_pad_to(cs.child_off, c, plan.pool_size)),
                    _put(_pad_to(cs.child_slot, c, b)),
                    _put(rel)])
                child_shapes.append((cs.ub, c))
            key = ((b, grp.m, grp.w, grp.u), la, tuple(child_shapes),
                   plan.pool_size, self.dtype)
            steps.append((key, tuple(_put(x) for x in a),
                          tuple(child_arrs), grp.batch, on_host))
        return steps

    # ---- compile-census integration (obs/compilestats.py) ---------------
    # The executor knows its FULL expected kernel set up front, so it
    # announces the per-key census labels at construction; a watchdog
    # fire mid-compile can then name the keys still PENDING (the
    # BENCH_r02 postmortem gap — 119 kernels, no record of which were
    # left).  Group granularity only: the level-traced programs are
    # per-wave aggregates with no stable per-key identity.

    _census_site = "stream._kernel"

    @staticmethod
    def _census_label(key) -> str:
        (b, m, w, u) = key[0]
        return f"lu b{b} m{m} w{w} u{u}"

    def _announce_keys(self) -> None:
        if self.granularity != "group":
            return
        COMPILE_STATS.announce(
            self._census_site,
            sorted({self._census_label(key)
                    for key, _, _, _, _ in self._steps}))

    def _get_kernel(self, key, pivot, args):
        """The program for one step key: the ahead-of-time compiled
        kernel when ``_build_kernels`` made one for this exact call
        signature, else the jitted one (which compiles inside its first
        call).  ``args`` is the exact call tuple (for AOT shape
        derivation in the mega subclass)."""
        fn = _kernel(*key, self.mesh, self.pool_partition, pivot,
                     self.gemm_prec)
        return _COMPILED.get((fn, _signature(args)), fn)

    def _build_kernels(self, pivot, avals, pool, thresh) -> None:
        """Compile every distinct single-device group kernel before the
        stream starts, in parallel threads (``compile_all``).  A cold
        factorization otherwise compiles its kernels one after another
        inside the dispatch loop: at n=110,592 on a v5e that is 89
        kernels and over ten minutes of serial compile.  Tracing and
        lowering stay on this thread; each build is recorded in the
        compile census as the kernel's first call would have been.
        Meshes keep the jitted path (their inputs' shardings are only
        settled inside the call), and host-share steps run jitted on
        the CPU device."""
        if self.mesh is not None:
            return
        todo = {}
        for key, a, child_arrs, _, on_host in self._steps:
            if on_host:
                continue
            args = (avals, pool, thresh, *a, *child_arrs)
            fn = _kernel(*key, None, False, pivot, self.gemm_prec)
            tkey = (fn, _signature(args))
            if tkey not in _COMPILED and tkey not in todo:
                todo[tkey] = (key, args)
        if not todo:
            return
        lowered = []
        for (fn, _), (key, args) in todo.items():
            censused = self._census_pending(key, pivot)
            if censused:
                self._audit_program(self._census_site,
                                    self._census_label(key), fn, args)
                _CENSUSED_KEYS.add(self._census_key(key, pivot))
            t0 = time.perf_counter()
            low = fn.lower(*(jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                  sharding=x.sharding)
                             for x in args))
            lowered.append((key, low, time.perf_counter() - t0, len(args),
                            censused))

        def build(i):
            # the lowering ran here; the compile runs on a worker thread
            key, _, t_lower, n_args, censused = lowered[i]
            if not censused:
                return contextlib.nullcontext()
            return COMPILE_STATS.build(self._census_site,
                                       self._census_label(key),
                                       n_args=n_args, before=t_lower)

        tkeys = list(todo)
        for i, exe, _ in compile_all(
                [low for _, low, _, _, _ in lowered], bool(self._progress),
                lambda i: self._census_label(lowered[i][0]), build):
            _COMPILED[tkeys[i]] = exe

    def _audit_program(self, site, label, fn, args) -> None:
        """Submit one program to the runtime IR auditor
        (SLU_TPU_VERIFY_PROGRAMS=1; no-op allocating nothing when off).
        Argnum 1 is the Schur pool — threaded linearly through the
        stream, dead after each call and donated by every kernel, which
        is exactly what SLU111 verifies."""
        from superlu_dist_tpu.utils.programaudit import maybe_audit
        maybe_audit(site, label, fn, args, dead=(1,),
                    mesh_axes=(tuple(self.mesh.axis_names)
                               if self.mesh is not None else ()))

    def _census_key(self, key, pivot) -> tuple:
        return ("group", key, self.mesh, self.pool_partition, pivot,
                self.gemm_prec)

    def _census_pending(self, key, pivot) -> bool:
        """True when this step's FIRST invocation will build (and should
        be timed into the census by the call loop)."""
        return self._census_key(key, pivot) not in _CENSUSED_KEYS

    def _census_build(self, key, pivot, n_args):
        """The build span and census record of a step's first call."""
        _CENSUSED_KEYS.add(self._census_key(key, pivot))
        return COMPILE_STATS.build(self._census_site, self._census_label(key),
                                   n_args=n_args)

    def _prep_avals(self, avals):
        """Upload/cast the pattern values (mega pads to its rung)."""
        return jnp.asarray(avals, dtype=self.dtype)

    def _ckpt_pool(self, pool):
        """The pool view a checkpoint frontier stores (mega strips its
        rung padding so frontiers stay executor-portable)."""
        return pool

    @property
    def n_kernels(self) -> int:
        if self.granularity == "level":
            return len({g.level for g in self.plan.groups})
        return len({key for key, _, _, _, _ in self._steps})

    @property
    def executed_flops(self) -> float:
        """Flops the device actually runs, bucket+batch padding included
        (plan.flops is the structural count — the reference's ops[FACT]).
        The ratio executed/structural is the padding overhead the MFU
        tuning fights (the reference's analog is its GEMM padding trick,
        dSchCompUdt-2Ddynamic.c:212-237)."""
        return float(sum(_bucket_len(g.batch, 1) * _front_flops(g.w, g.u)
                         for g in self.plan.groups))

    @staticmethod
    def _level_flat(entries) -> tuple:
        """One level's index maps + child tables flattened into the
        program-argument tuple ``_level_fn`` expects (5 assembly arrays
        then 3 per child set, per entry — the layout is static program
        STRUCTURE, the arrays are program INPUTS)."""
        return tuple(x for _, a, child_arrs, _, _ in entries
                     for x in (*a, *child_arrs))

    def _level_fn(self, level, entries):
        """One jitted program running every group of `level`.  The index
        maps are passed as program ARGUMENTS (see _level_flat), not
        closed over: a captured device array becomes a jaxpr CONSTANT,
        so the compiled program identifies the matrix — the per-matrix-
        capture pattern slulint SLU112 polices."""
        from superlu_dist_tpu.ops.dense import pivot_kernel
        pivot = pivot_kernel()    # resolved OUTSIDE the traced body: the
        # choice is the cache key (slulint SLU105); the gemm tier is an
        # executor-lifetime constant (latched in the constructor), so
        # (level, pivot) stays a sufficient key here
        gemm_prec = self.gemm_prec
        fn = self._level_fns.get((level, pivot))
        if fn is not None:
            return fn
        from superlu_dist_tpu.numeric.factor import pool_spec
        psh = (pool_spec(self.mesh, self.pool_partition)
               if self.mesh is not None else None)

        front_sharding = pivot_sharding = replicated = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            front_sharding = NamedSharding(self.mesh,
                                           P("snode", None, "panel"))
            pivot_sharding = NamedSharding(self.mesh,
                                           P("snode", None, None))
            replicated = NamedSharding(self.mesh, P(None, None))

        metas = tuple((key, len(child_arrs))
                      for key, _, child_arrs, _, _ in entries)

        def run(avals, pool, thresh, *flat):
            outs = []
            tiny = jnp.zeros((), jnp.int32)
            i = 0
            for key, n_child in metas:
                (dims, l_a, child_shapes, _, _) = key
                a = flat[i:i + 5]
                child_arrs = flat[i + 5:i + 5 + n_child]
                i += 5 + n_child
                if psh is not None:
                    pool = jax.lax.with_sharding_constraint(pool, psh)
                children = [(ub, child_arrs[3 * j], child_arrs[3 * j + 1],
                             child_arrs[3 * j + 2])
                            for j, (ub, _) in enumerate(child_shapes)]
                out, pool, t = group_step(
                    dims, avals, pool, thresh, *a, children,
                    front_sharding=front_sharding,
                    pivot_sharding=pivot_sharding, replicated=replicated,
                    pivot=pivot, gemm_prec=gemm_prec)
                outs.append(out)
                tiny = tiny + t
            if psh is not None:
                pool = jax.lax.with_sharding_constraint(pool, psh)
            return outs, pool, tiny

        fn = jax.jit(run, donate_argnums=(1,))
        self._level_fns[(level, pivot)] = fn
        return fn

    def __call__(self, avals, thresh):
        plan = self.plan
        pool = jnp.zeros(self._pool_len, dtype=self.dtype)
        avals = self._prep_avals(avals)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from superlu_dist_tpu.numeric.factor import pool_spec
            rep = NamedSharding(self.mesh, P(None))
            pool = jax.device_put(pool,
                                  pool_spec(self.mesh, self.pool_partition))
            avals = jax.device_put(avals, rep)
        # kernel-shape trace (the reference's PROFlevel GEMM trace,
        # pdgstrf.c:380-387 -> dgemm_mnk.dat): per-group synchronous
        # timing under the file tracer (SLU_TPU_TRACE), whose kernel spans
        # must sum to the factor wall time, which only per-group blocking
        # guarantees.  Blocking serializes the async dispatch stream, so
        # such runs measure per-kernel cost, not end-to-end overlap; the
        # flight recorder and the profiler sink never block (a profiler
        # trace of the named programs gives per-kernel device time)
        self._tracer = tracer = get_tracer()
        profile = tracer.profiling
        # SLU_TPU_PROGRESS=K: log every K groups/levels issued (async
        # issue order, not completion) — hours-long runs are otherwise
        # silent between plan build and the final block_until_ready
        progress = env_int("SLU_TPU_PROGRESS")
        self._progress = max(progress, 0)
        self._offload_wait = 0.0
        builds0 = self._retrace_begin()
        if self.granularity == "level":
            return self._call_levels(avals, pool, thresh, profile, builds0)
        fronts = []
        tiny = jnp.zeros((), jnp.int32)
        t_issue0 = time.perf_counter()
        from superlu_dist_tpu.ops.dense import pivot_kernel
        pivot = pivot_kernel()
        # host-share prologue: the leading levels' kernels run on the CPU
        # device, so pool/avals/thresh start there; the first device group
        # triggers the one H2D handoff (mirrors the reference keeping the
        # leading blocks' GEMMs on the CPU while the accelerator streams,
        # dSchCompUdt-cuda.c:253-294)
        avals_dev, thresh_dev = avals, thresh
        self._build_kernels(pivot, avals, pool, thresh)
        on_host_now, avals, thresh, pool = self._host_prologue(
            avals, thresh, pool)
        tiny_host = 0
        # checkpoint resume: splice a durable frontier in — the first
        # `start` groups' panels come from the checkpoint and the pool
        # restarts from the saved boundary state, so the remaining
        # groups run the IDENTICAL arithmetic an uninterrupted run
        # would (bitwise; scripts/check_crash_resume.py pins it)
        resume, self.resume = self.resume, None
        start = tiny_resumed = 0
        if resume is not None:
            start, fronts, pool, tiny_resumed = self._apply_resume(
                resume, pool)
        for gi, (key, a, child_arrs, nreal, on_host) in \
                enumerate(self._steps):
            if gi < start:
                continue
            if self.deadline is not None:
                self._deadline_poll("streamed factorization")
            if on_host_now and not on_host:
                tiny_host, pool = self._host_handoff(tiny, pool)
                tiny = jnp.zeros((), jnp.int32)
                avals, thresh = avals_dev, thresh_dev
                on_host_now = False
            kern = self._get_kernel(key, pivot,
                                    (avals, pool, thresh, *a, *child_arrs))
            # compile census: the FIRST invocation per shape key runs the
            # synchronous trace+lower+compile inside the dispatch — time
            # it (no extra blocking; execution stays async).  The mega
            # subclass AOT-builds inside _get_kernel instead and reports
            # the exact trace/lower/compile split there.
            cold = self._census_pending(key, pivot)
            if cold:
                self._audit_program(self._census_site,
                                    self._census_label(key), kern,
                                    (avals, pool, thresh, *a, *child_arrs))
            if self._progress and gi % self._progress == 0:
                print(f"[stream] issuing group {gi}/{len(self._steps)} "
                      f"(+{time.perf_counter() - t_issue0:.1f}s)",
                      file=sys.stderr, flush=True)
            if profile or tracer.enabled:
                t0 = time.perf_counter()
            with (self._census_build(key, pivot, 8 + len(child_arrs))
                  if cold else contextlib.nullcontext()):
                (lp, up), pool, t = kern(avals, pool, thresh, *a,
                                         *child_arrs)
            if tracer.enabled:
                # async-issue span: how long the DISPATCH took (Python +
                # transfer setup), before any blocking — the
                # dispatch-bound-vs-compute-bound split per group
                tracer.complete(f"issue g{gi}", "dispatch", t0,
                                time.perf_counter() - t0, group=gi,
                                level=int(plan.groups[gi].level))
            if profile:
                jax.block_until_ready(lp)
                dt = time.perf_counter() - t0
                (b, m, w, u) = key[0]
                grp = plan.groups[gi]
                self._trace_kernel(t0, dt, grp.level, b, m, w, u,
                                   grp.batch, on_host)
            self._emit_front(fronts, lp, up, nreal, on_host)
            tiny = tiny + t
            if self.checkpoint is not None:
                # frontier bookkeeping (interval flushes inside note);
                # BEFORE the chaos hook so an injected kill at group gi
                # leaves gi's interval checkpoint durable
                self.checkpoint.note(gi, fronts, self._ckpt_pool(pool),
                                     tiny)
            if self.chaos is not None:
                self.chaos.on_group(gi)
        tiny = tiny + tiny_host + tiny_resumed
        # dispatch-gap instrumentation (the PROFlevel comm-split analog,
        # pdgstrf.c:1930-1951): time spent ISSUING the async stream.  If
        # this approaches the end-to-end factor time, the run is
        # dispatch-bound (Python + transfer overhead), not compute-bound.
        self.last_dispatch_seconds = time.perf_counter() - t_issue0
        self.last_offload_wait_seconds = self._offload_wait
        self._retrace_end(builds0)
        return self._finalize_fronts(fronts), tiny

    def _apply_resume(self, resume, pool):
        """Validate and splice a ResumeState: returns (start, fronts,
        pool, tiny_resumed).  Mesh-sharded and host-share runs have no
        single durable pool boundary to restore into — refused."""
        from superlu_dist_tpu.utils.errors import SuperLUError
        if self.mesh is not None or self._host_levels:
            raise SuperLUError(
                "checkpoint resume is not supported on a mesh-sharded "
                "or host-share factorization — refactor from scratch")
        start = int(resume.k)
        if start > len(self._steps):
            raise SuperLUError(
                f"resume frontier k={start} exceeds this plan's "
                f"{len(self._steps)} groups")
        fronts = [(lp, up) for lp, up in resume.fronts]
        pool = jnp.asarray(resume.pool, dtype=self.dtype)
        if self.checkpoint is not None:
            self.checkpoint.tiny_base = int(resume.tiny)
        return start, fronts, pool, int(resume.tiny)

    def _deadline_poll(self, where: str) -> None:
        """Cooperative deadline check at a group boundary: the latest
        consistent frontier is flushed BEFORE the structured raise, so
        cancellation always leaves a resumable checkpoint behind (and
        on the multi-rank path the poll's flag allreduce makes the
        raise collective — see utils/deadline.py)."""
        ck = self.checkpoint
        self.deadline.poll(
            where=where,
            on_expire=(None if ck is None
                       else (lambda: ck.flush_latest("deadline"))))

    def _retrace_begin(self) -> int:
        """Kernel-build counter snapshot (per granularity's cache)."""
        if self.granularity == "level":
            return len(self._level_fns)
        return _kernel.cache_info().misses

    def _retrace_end(self, before: int) -> None:
        built = self._retrace_begin() - before
        self.last_kernel_builds = built
        self.last_retraces = 0
        if self._warmed and built:
            # a warmed executor re-ran the same plan and still compiled:
            # some cache-key input changed under us (dynamic SLU105)
            self.last_retraces = built
            RETRACE_SENTINEL.record(f"StreamExecutor[{self.granularity}]",
                                    built, self._tracer)
        self._warmed = True

    def _trace_kernel(self, t0, dt, level, b, m, w, u, nreal, host,
                      aggregate=False, executed=None, structural=None):
        """Structured kernel-shape record (the dgemm_mnk.dat analog):
        executed vs structural flops and the padding ratio per dispatch,
        so MFU attribution needs no stderr scraping."""
        tr = self._tracer
        if not tr.enabled:
            return
        if executed is None:
            executed = float(b) * _front_flops(w, u)
        if structural is None:
            structural = float(nreal) * _front_flops(w, u)
        tr.complete(f"lu b{b} m{m} w{w} u{u}", "kernel", t0, dt,
                    level=int(level), batch=int(nreal),
                    padded_batch=int(b), m=int(m), w=int(w), u=int(u),
                    gemm_prec=self.gemm_prec_resolved,
                    host=bool(host), aggregate=bool(aggregate),
                    executed_flops=float(executed),
                    structural_flops=float(structural),
                    padding=round(float(executed)
                                  / max(float(structural), 1.0), 4))

    def _host_prologue(self, avals, thresh, pool):
        """(active, avals, thresh, pool): when the plan opens with
        host-share levels, commit the stream inputs to the cpu device.
        Shared by both granularities so their handoff logic cannot
        diverge."""
        if not (self._steps and self._steps[0][4]):
            return False, avals, thresh, pool
        return (True, jax.device_put(avals, self._cpu_dev),
                jax.device_put(thresh, self._cpu_dev),
                jax.device_put(pool, self._cpu_dev))

    @staticmethod
    def _host_handoff(tiny, pool):
        """End of the host prefix: sync its tiny-pivot count on the cheap
        host stream and move the pool to the accelerator (the ONE H2D
        transfer of the split)."""
        return int(tiny), jax.device_put(np.asarray(pool))

    def _emit_front(self, fronts, lp, up, nreal, on_host=False):
        """Append one group's factored panels; in offload mode start the
        D2H transfer now (it overlaps the following kernels — the
        copy-back stream of the reference's GPU path,
        dSchCompUdt-cuda.c:238-241) and materialize with a lag window so
        the device never holds more than a few groups of panels."""
        if lp.shape[0] != nreal:
            lp, up = lp[:nreal], up[:nreal]
        if on_host:
            # host-share groups: panels already live on the cpu device;
            # keep them async here (a per-group np.asarray would block the
            # host stream) — _finalize_fronts materializes the prefix
            fronts.append((lp, up))
        elif self.offload == "host":
            lp.copy_to_host_async()
            up.copy_to_host_async()
            fronts.append((lp, up))
            i = len(fronts) - 1 - self._lag
            # the lag window must not reach into the host-share prefix:
            # materializing those cpu-device panels here would block on
            # host-stream COMPUTE (not D2H) and corrupt the comm split —
            # _finalize_fronts handles the prefix
            if i >= self._n_host_groups:
                dlp, dup = fronts[i]
                if not isinstance(dlp, np.ndarray):
                    t0 = time.perf_counter()
                    fronts[i] = (np.asarray(dlp), np.asarray(dup))
                    dt = time.perf_counter() - t0
                    self._offload_wait += dt
                    if self._tracer.enabled:
                        self._tracer.complete(
                            f"offload g{i}", "host-offload", t0, dt,
                            group=i, bytes=int(fronts[i][0].nbytes
                                               + fronts[i][1].nbytes))
                    if self.check_finite:
                        self._sentinel_check(i, *fronts[i])
        else:
            fronts.append((lp, up))

    def _sentinel_check(self, gi, lp, up):
        """Trip NumericBreakdownError if group `gi`'s materialized panels
        carry NaN/Inf — the mid-stream half of the non-finite sentinel
        (the end-of-run half lives in factor.numeric_factorize)."""
        if np.isfinite(lp).all() and np.isfinite(up).all():
            return
        from superlu_dist_tpu.utils.errors import NumericBreakdownError
        grp = self.plan.groups[gi]
        sn_start = self.plan.sf.sn_start
        nf = ~np.isfinite(lp.reshape(lp.shape[0], -1)).all(axis=1)
        nf |= ~np.isfinite(up.reshape(lp.shape[0], -1)).all(axis=1)
        sns = np.asarray(grp.sns)[np.nonzero(nf)[0]]
        sn = int(sns[np.argmin(sn_start[sns])])
        # durability before diagnosis: flush the latest consistent
        # frontier FIRST, so the error construction's flight-recorder
        # dump can reference the checkpoint it left behind
        ck_path = (self.checkpoint.flush_latest("numeric-breakdown")
                   if self.checkpoint is not None else None)
        err = NumericBreakdownError(supernode=sn, col=int(sn_start[sn]),
                                    where="streamed factorization")
        err.checkpoint_path = ck_path
        raise err

    def _finalize_fronts(self, fronts):
        if self.offload == "host" or self._n_host_groups:
            # offload mode: everything to numpy.  Host-share only: just
            # the leading host-group prefix (the trailing device fronts
            # stay resident so the device solve keeps working on them).
            fronts = [
                (lp, up) if isinstance(lp, np.ndarray)
                or (self.offload != "host" and i >= self._n_host_groups)
                else (np.asarray(lp), np.asarray(up))
                for i, (lp, up) in enumerate(fronts)]
        return tuple(fronts)

    def _call_levels(self, avals, pool, thresh, profile, builds0=0):
        """Level-granularity execution: one dispatch per elimination
        level (see __init__)."""
        import itertools
        if self.resume is not None:
            from superlu_dist_tpu.utils.errors import SuperLUError
            raise SuperLUError(
                "checkpoint resume requires granularity='group' (the "
                "level-traced programs have no per-group entry points)")
        plan = self.plan
        fronts = []
        tiny = jnp.zeros((), jnp.int32)
        pairs = list(zip(plan.groups, self._steps))
        avals_dev, thresh_dev = avals, thresh
        on_host_now, avals, thresh, pool = self._host_prologue(
            avals, thresh, pool)
        tiny_host = 0
        for level, chunk in itertools.groupby(pairs,
                                              key=lambda p: p[0].level):
            if self.deadline is not None:
                self._deadline_poll("streamed factorization")
            chunk = list(chunk)
            entries = tuple(step for _, step in chunk)
            lv_host = entries[0][4]
            if on_host_now and not lv_host:
                tiny_host, pool = self._host_handoff(tiny, pool)
                tiny = jnp.zeros((), jnp.int32)
                avals, thresh = avals_dev, thresh_dev
                on_host_now = False
            n_fns = len(self._level_fns)
            fn = self._level_fn(level, entries)
            flat = self._level_flat(entries)
            # a fresh jitted program means the next call compiles it —
            # account the build in the compile census (sync compile
            # inside the dispatch, execution stays async)
            cold = len(self._level_fns) > n_fns
            if cold:
                self._audit_program(
                    "stream._level_fn", f"level{level} g{len(entries)}",
                    fn, (avals, pool, thresh, *flat))
            if self._progress:
                print(f"[stream] issuing level {level} "
                      f"({len(entries)} groups)", file=sys.stderr,
                      flush=True)
            tracer = self._tracer
            if profile or tracer.enabled:
                t0 = time.perf_counter()
            with (COMPILE_STATS.build("stream._level_fn",
                                      f"level{level} g{len(entries)}",
                                      n_args=3)
                  if cold else contextlib.nullcontext()):
                outs, pool, t = fn(avals, pool, thresh, *flat)
            tiny = tiny + t
            if tracer.enabled:
                tracer.complete(f"issue lvl{level}", "dispatch", t0,
                                time.perf_counter() - t0,
                                level=int(level), groups=len(entries))
            if profile:
                jax.block_until_ready(outs)
                dt = time.perf_counter() - t0
                gflop = sum(float(_front_flops(g.w, g.u)) * g.batch
                            for g, _ in chunk) / 1e9
                # a LEVEL aggregate, not one kernel's shape: m/w/u are
                # maxima over the level's heterogeneous groups
                self._trace_kernel(
                    t0, dt, level,
                    sum(key[0][0] for key, *_ in entries),
                    max(g.m for g, _ in chunk),
                    max(g.w for g, _ in chunk),
                    max(g.u for g, _ in chunk),
                    sum(g.batch for g, _ in chunk), lv_host,
                    aggregate=True,
                    executed=float(sum(
                        key[0][0] * _front_flops(key[0][2], key[0][3])
                        for key, *_ in entries)),
                    structural=gflop * 1e9)
            for (grp, (_, _, _, nreal, g_host)), (lp, up) in zip(chunk, outs):
                self._emit_front(fronts, lp, up, nreal, g_host)
            if fronts:
                # wave boundary: the pool now corresponds exactly to the
                # frontier len(fronts) — the only consistent checkpoint
                # boundary this granularity has (group-mode resume can
                # still consume it: frontiers are group-aligned)
                if self.checkpoint is not None:
                    self.checkpoint.note(len(fronts) - 1, fronts,
                                         self._ckpt_pool(pool), tiny)
                if self.chaos is not None:
                    self.chaos.on_group(len(fronts) - 1)
        self.last_offload_wait_seconds = self._offload_wait
        self._retrace_end(builds0)
        return self._finalize_fronts(fronts), tiny + tiny_host

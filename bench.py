#!/usr/bin/env python
"""Benchmark: sparse LU numeric-factorization GFLOPS, TPU vs host CPU.

The metric mirrors the reference's headline number — factor Mflops printed
by PStatPrint (SRC/util.c:513-518) — on the BASELINE.md config-4 matrix
class (7-pt 3D Poisson).  The numeric factorization runs entirely on the
device via the streamed executor (numeric/stream.py).

vs_baseline is the wall-clock factorization speedup over serial SuperLU
with host CPU BLAS (scipy.sparse.linalg.splu — the same code family as the
reference) factoring the identical matrix on this machine (north-star
target: >= 4x CPU-BLAS factorization, BASELINE.json).  The reference's
distributed pdgstrf on one node is the same computation plus MPI overhead,
so serial SuperLU is the stronger (fairer) baseline.  Note the dtype
asymmetry is part of the design under measure: the TPU path factors in f32
and recovers f64 accuracy via iterative refinement (GESP + IR, SURVEY.md
§7 hard-part 1); the residual printed is AFTER refinement and must be at
reference accuracy.

Robustness (the pdtest discipline, TEST/pdtest.c — count failures, still
report): ONE JSON line always prints.  A watchdog emits whatever has been
measured if the wall budget expires, marked "timeout", and exits 1.  The
bench runs on the backend jax selects (JAX_PLATFORMS=cpu pins the CPU);
it never swaps backends on its own, and every row names its backend.

Prints ONE JSON line:
  {"metric": ..., "value": GFLOPS, "unit": "GFLOP/s", "vs_baseline": ...}

Env knobs: BENCH_NX (grid edge, default 48 -> n=110592), BENCH_REPS,
BENCH_DEADLINE_S (watchdog, default 1350), BENCH_PEAK_F32_TFLOPS (MFU
denominator), BENCH_MESH (an 'RxC' mesh spec, e.g. 1x8: factor/solve run over a real
jax.Mesh through the shard_map SPMD tier and the row carries
mesh_shape/n_devices/spmd — virtual CPU devices when the backend is
cpu, so MULTICHIP rows are real measurements off-hardware too).
"""

import json
import os
import sys
import threading
import time

import numpy as np

RESULT = {"metric": "lu_factor_gflops_poisson3d", "value": None,
          "unit": "GFLOP/s", "vs_baseline": None, "phase": "startup"}
_PRINTED = threading.Lock()
_DONE = False


def _emit(final: bool):
    global _DONE
    with _PRINTED:
        if _DONE:
            return
        snap = dict(RESULT)      # snapshot: main thread mutates RESULT
        # rank-failure tolerance telemetry (parallel/recover.py): how
        # many shrink/respawn recoveries this run absorbed, and whether
        # the row's numbers rest on a recovered solve — 0/False on the
        # single-process bench unless an embedded FT driver ran
        try:
            from superlu_dist_tpu.parallel.recover import FT_EVENTS
            snap["ft_events"] = len(FT_EVENTS)
            snap["recovered"] = bool(FT_EVENTS)
        except Exception:
            snap["ft_events"] = 0
            snap["recovered"] = False
        if not final:
            snap["timeout"] = True
        print(json.dumps(snap), flush=True)
        _DONE = True             # only after a successful print


def _log(msg: str):
    print(f"[bench +{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()
# the one import-time knob read: routed through the central registry
# (utils/options.py) like every SLU_TPU_* knob, so slulint SLU104 and the
# generated knob table cover the bench's watchdog too (bench.py sits at
# the repo root, so the package resolves from the script directory)
from superlu_dist_tpu.utils.options import env_float  # noqa: E402

DEADLINE = env_float("BENCH_DEADLINE_S")

_PHASE_T = [T0]


def _set_phase(name: str):
    """Advance RESULT["phase"], folding the previous phase's elapsed
    wall time into RESULT["phase_seconds"] — so a watchdog fire reports
    where the budget WENT, not just where the run died (the BENCH_r02
    n=110592 lesson: 'died in factor-compile' with no breakdown)."""
    now = time.perf_counter()
    prev = RESULT.get("phase")
    secs = RESULT.setdefault("phase_seconds", {})
    if prev is not None:
        secs[prev] = round(secs.get(prev, 0.0) + now - _PHASE_T[0], 3)
    RESULT["phase"] = name
    _PHASE_T[0] = now


def _watchdog():
    time.sleep(DEADLINE)
    _log(f"watchdog fired in phase '{RESULT.get('phase')}' — emitting "
         "partial result")
    try:
        # fold the in-progress phase's elapsed time in, attach the
        # compile census collected so far, and leave the flight-recorder
        # postmortem (none of this may block the JSON line)
        _set_phase(RESULT.get("phase"))
        from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
        blk = COMPILE_STATS.block(top=16)
        RESULT.setdefault("compile_seconds", blk["seconds"])
        RESULT.setdefault("compile_census", blk["census"])
        # a factor-compile death names the shape keys still UNCOMPILED
        # (announced by the executor, retired per build): the census
        # delta the next BENCH_r02-style postmortem needs to blame the
        # offending buckets instead of just counting them
        pending = COMPILE_STATS.pending()
        if pending:
            RESULT.setdefault("pending_kernels", pending)
        _aud = COMPILE_STATS.audit_block()
        if _aud["programs"]:
            RESULT.setdefault("programs_audited", _aud["programs"])
            RESULT.setdefault("donation_coverage_pct",
                              _aud["donation_coverage_pct"])
            RESULT.setdefault("baked_const_bytes",
                              _aud["baked_const_bytes"])
        if _aud["programs_sharding_audited"]:
            RESULT.setdefault("programs_sharding_audited",
                              _aud["programs_sharding_audited"])
            RESULT.setdefault("peak_bytes_est", _aud["peak_bytes_est"])
            RESULT.setdefault("replicated_bytes",
                              _aud["replicated_bytes"])
        # durable frontier FIRST (persist/checkpoint.py): flush whatever
        # the factor loop completed, record the bundle path and its
        # resume eligibility in the row — the next BENCH run of this
        # matrix resumes from it instead of recompiling/refactoring from
        # zero (the BENCH_r02 n=110592 death left nothing reusable)
        from superlu_dist_tpu.persist.checkpoint import (
            flush_active, last_checkpoint)
        ck = flush_active("bench-watchdog") or last_checkpoint()
        if ck:
            RESULT["checkpoint_path"] = ck
            try:
                from superlu_dist_tpu.persist.checkpoint import peek
                meta = peek(ck)
                RESULT["resume_eligible"] = True
                RESULT["checkpoint_groups"] = meta.get("k")
            except Exception:
                RESULT["resume_eligible"] = False
            _log(f"factor checkpoint: {ck} "
                 f"(resume_eligible={RESULT.get('resume_eligible')})")
        from superlu_dist_tpu.obs.flightrec import get_flightrec
        fr = get_flightrec()
        if fr.enabled:
            p = fr.dump("bench-watchdog",
                        detail=f"phase={RESULT.get('phase')}",
                        extra={"phase_seconds": RESULT.get("phase_seconds"),
                               "metric": RESULT.get("metric"),
                               "checkpoint": ck})
            _log(f"flight-recorder postmortem: {p}")
    except Exception as e:                          # pragma: no cover
        _log(f"watchdog telemetry failed: {type(e).__name__}: {e}")
    try:
        _emit(final=False)
    finally:
        # a run cut by its watchdog is a failed run: the partial row is
        # telemetry, and the exit code says so
        os._exit(1)


def main():
    threading.Thread(target=_watchdog, daemon=True).start()

    # BENCH_MESH=RxC: the multichip bench mode — factor/solve run over a
    # real jax.Mesh (virtual CPU devices when the backend is cpu, chips
    # on TPU) through the shard_map SPMD tier (parallel/spmd.py), and
    # the row carries mesh_shape/n_devices/spmd instead of being a
    # single-device row.  The device-count config must land BEFORE the
    # backend initializes.
    MESH_SPEC = os.environ.get("BENCH_MESH", "")
    MESH_DIMS = None
    if MESH_SPEC:
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        from _common import parse_mesh_spec
        MESH_DIMS = parse_mesh_spec(MESH_SPEC)
        # cpu-platform only (a TPU brings its real chips): XLA snapshots
        # XLA_FLAGS at backend init, which has not happened yet
        if "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={MESH_DIMS[2]}")

    import jax
    import jax.numpy as jnp

    from superlu_dist_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()

    # flight recorder (obs/flightrec.py): the bench flies it ALWAYS ON —
    # a watchdog kill or mid-factor breakdown must leave a postmortem
    # (last events, phase stack, compile census) instead of nothing (the
    # BENCH_r02 outcome).  SLU_TPU_FLIGHTREC overrides the dump path;
    # installed BEFORE the first get_tracer() so the tracer composition
    # feeds the ring from every existing instrumentation site.
    from superlu_dist_tpu.obs import flightrec
    fr = flightrec.get_flightrec()
    if not fr.enabled:
        fr = flightrec.FlightRecorder(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), ".cache",
            "bench_flightrec_%p.json"))
        flightrec.install(fr, arm_signals=True)
    RESULT["flightrec"] = fr.dump_path

    # structured tracing (obs/trace.py): SLU_TPU_TRACE=<path> turns this
    # run into one self-describing artifact — phase spans from this
    # function, dispatch/kernel-shape spans from the executors, comm
    # spans for the host<->device transfers (docs/OBSERVABILITY.md)
    from superlu_dist_tpu.obs.trace import get_tracer
    tracer = get_tracer()
    if tracer.enabled and tracer.path:
        RESULT["trace"] = tracer.path

    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.utils.options import Options
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.numeric.stream import StreamExecutor
    from superlu_dist_tpu.numeric.factor import NumericFactorization
    from superlu_dist_tpu.drivers.gssvx import LUFactorization
    from superlu_dist_tpu.refine.ir import iterative_refinement

    NX = int(os.environ.get("BENCH_NX", "48"))   # n = NX^3 = 110,592:
    # large enough that the big separator fronts drive the MXU (the r1
    # bench at NX=24 was latency-bound, VERDICT weak #3); with compact
    # (lpanel, upanel) factor storage the whole factorization fits
    # single-chip HBM (~8 GB at NX=48 vs 16 GB on v5e)
    REPS = int(os.environ.get("BENCH_REPS", "3"))
    # bfloat16 engages the MXU's native-rate passes (~4x the f32-HIGHEST
    # rate); IR still recovers f64 residuals on well-conditioned systems
    # (more steps).  f32 is the safe default.
    DTYPE = os.environ.get("BENCH_DTYPE", "float32")
    # MFU denominator (utils/peaks.py): per-backend/per-GEMM-tier peak —
    # TPU kinds tabulated, CPU calibrated with a micro-GEMM — so a CPU
    # row never divides by a TPU constant and prints mfu_pct 0.0 (the
    # historical honesty bug).  SLU_TPU_PEAK_GFLOPS overrides; the
    # legacy BENCH_PEAK_F32_TFLOPS knob still wins when explicitly set.
    from superlu_dist_tpu.ops.dense import gemm_precision
    from superlu_dist_tpu.utils.peaks import detect_peak_gflops
    GEMM_PREC = gemm_precision(None)
    RESULT["gemm_precision"] = GEMM_PREC
    _legacy_peak = env_float("BENCH_PEAK_F32_TFLOPS", default=0.0)
    if _legacy_peak > 0:
        PEAK_GF, PEAK_SRC = _legacy_peak * 1e3, "env:BENCH_PEAK_F32_TFLOPS"
    else:
        PEAK_GF, PEAK_SRC = detect_peak_gflops(GEMM_PREC)
    RESULT["peak_gflops"] = round(PEAK_GF, 1)
    RESULT["peak_source"] = PEAK_SRC
    # Blocking defaults are backend-specific.  TPU: wide supernodes feed
    # the MXU (SURVEY.md §7 step 10 — the reference's NSUP=128 is
    # CPU-cache-sized) and keep the streamed executor's kernel count
    # small.  CPU fallback: no MXU to feed, so minimize PADDING instead —
    # tighter buckets/amalgamation cut executed/structural flops from
    # 1.37x to 1.09x and put the fused executor at 1.18x scipy splu at
    # NX=32 (the r4 CPU sweep; r3's group-streamed CPU row lost at
    # 0.66x).  Env-overridable for on-hardware tuning sweeps.
    _cpu = jax.default_backend() == "cpu"
    RELAX = int(os.environ.get("BENCH_RELAX", "128" if _cpu else "256"))
    MAX_SUPER = int(os.environ.get("BENCH_MAXSUPER",
                                   "256" if _cpu else "1024"))
    MIN_BUCKET = int(os.environ.get("BENCH_MINBUCKET",
                                    "16" if _cpu else "32"))
    GROWTH = float(os.environ.get("BENCH_GROWTH", "1.05" if _cpu else "1.3"))
    # fill-tolerant amalgamation (symbfact.amalgamate_supernodes) is the
    # round-3 MFU lever: at NX=48 it cuts 10707 supernodes/325 levels/119
    # kernels to 587/13/~45 and the executed-over-structural flop ratio
    # from 15.7x to ~1.7x
    AMALG = float(os.environ.get("BENCH_AMALG", "1.05" if _cpu else "1.2"))
    RESULT["blocking"] = [RELAX, MAX_SUPER, MIN_BUCKET, GROWTH, AMALG]

    backend = jax.default_backend()
    RESULT["backend"] = backend
    MESH = None
    if MESH_DIMS:
        from superlu_dist_tpu.parallel.grid import gridinit
        MESH = gridinit(MESH_DIMS[0], MESH_DIMS[1]).mesh
        RESULT["mesh_shape"] = [MESH_DIMS[0], MESH_DIMS[1]]
        RESULT["n_devices"] = MESH_DIMS[2]
        _log(f"mesh mode: {MESH_DIMS[0]}x{MESH_DIMS[1]} "
             f"({MESH_DIMS[2]} {backend} devices)")
    _set_phase("prepare")
    t_phase = time.perf_counter()

    # BENCH_MATRIX=geo3d swaps in the irregular FEM-like family
    # (random_geometric_3d, the audikw_1-class surrogate — BASELINE
    # config 5) at the same n = NX^3, guarding blocking choices against
    # overfitting to the regular Poisson stencil
    MATRIX = os.environ.get("BENCH_MATRIX", "poisson3d")
    if MATRIX not in ("poisson3d", "geo3d"):
        raise SystemExit(f"BENCH_MATRIX={MATRIX!r}: expected poisson3d|geo3d")
    if MATRIX == "geo3d":
        from superlu_dist_tpu.models.gallery import random_geometric_3d
        a = random_geometric_3d(NX ** 3)
    else:
        a = poisson3d(NX)
    opts = Options()
    sym = symmetrize_pattern(a)
    col_order = get_perm_c(opts, a, sym)
    sf = symbolic_factorize(sym, col_order, relax=RELAX,
                            max_supernode=MAX_SUPER, amalg_tol=AMALG)
    # executor granularity resolved BEFORE the plan: the mega executor
    # wants the shape-key set CLOSED at plan build (numeric/plan.py —
    # the O(1)-compiled-programs contract), which an explicit
    # SLU_TPU_BUCKET_CLOSED setting can still override either way
    gran = os.environ.get(
        "BENCH_GRANULARITY",
        ("auto" if MESH is not None            # -> spmd via get_executor
         else "fused" if backend == "cpu" else "group"))
    _closed = (True if gran == "mega"
               and "SLU_TPU_BUCKET_CLOSED" not in os.environ else None)
    plan = build_plan(sf, min_bucket=MIN_BUCKET, growth=GROWTH,
                      closed=_closed)
    RESULT["bucket_set_digest"] = plan.bucket_set_digest()
    RESULT["bucket_closed"] = plan.closed
    if plan.pool_size >= 2 ** 31 and not jax.config.jax_enable_x64:
        # beyond-int32 pool (n>=~600k at f32): indices must stay int64
        # (the reference's XSDK_INDEX_SIZE=64 tier); costs some index
        # bandwidth on device, required for correctness
        _log(f"pool_size {plan.pool_size:.3g} >= 2^31 — enabling x64 "
             "index mode")
        jax.config.update("jax_enable_x64", True)
    # numpy has no bf16, so that case stages through f32; every other
    # dtype keeps full precision.  The executor casts to DTYPE on upload;
    # the GESP threshold uses DTYPE's own epsilon.
    host_dt = np.float32 if DTYPE == "bfloat16" else np.dtype(DTYPE)
    avals_np = sym.data[sf.value_perm].astype(host_dt)
    eps = float(jnp.finfo(jnp.dtype(DTYPE)).eps)
    thresh_np = np.asarray(np.sqrt(eps) * a.norm_max(), host_dt)
    n = a.n_rows
    RESULT["metric"] = f"lu_factor_gflops_{MATRIX}_n{n}_{DTYPE}"
    RESULT["flops"] = plan.flops
    # dispatch-schedule telemetry (numeric/plan.py): scheduler name,
    # group count before/after dataflow aggregation, mean fronts per
    # dispatch and the dependent-group critical path
    sched = plan.schedule_stats(itemsize=host_dt.itemsize)
    RESULT["schedule"] = sched["schedule"]
    RESULT["n_groups"] = sched["n_groups"]
    RESULT["n_level_groups"] = sched["n_level_groups"]
    RESULT["occupancy"] = sched["occupancy"]
    RESULT["critical_path"] = sched["critical_path"]
    # irregular gather/scatter traffic (data-movement honesty next to
    # the flop padding factor)
    RESULT["bytes_moved"] = sched["bytes_moved"]
    _log(f"prepared n={n} schedule={sched['schedule']} "
         f"groups={sched['n_groups']} (level {sched['n_level_groups']}) "
         f"occupancy={sched['occupancy']} flops={plan.flops / 1e9:.0f} GF")

    tracer.complete("prepare", "phase", t_phase,
                    time.perf_counter() - t_phase, n=n,
                    groups=len(plan.groups))
    _set_phase("factor-compile")
    t_phase = time.perf_counter()
    # compile census window (obs/compilestats.py): everything the warm
    # call below builds lands in compile_seconds + the per-bucket census
    # — the ROADMAP item 3 acceptance fields
    from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
    _comp0 = COMPILE_STATS.marker()
    # BENCH_GRANULARITY: "group" (one kernel per shape key, streamed),
    # "level" (one program per elimination level), "mega" (ONE
    # data-driven program per closed shape bucket, numeric/mega.py —
    # the O(1)-compiled-programs executor for the TPU compile wall), or
    # "fused" (the WHOLE factorization as one XLA program — viable
    # again now that amalgamation leaves ~45 groups; zero dispatch
    # overhead, XLA schedules across groups).  Default follows
    # get_executor's "auto" rule (numeric/factor.py): fused on CPU —
    # per-group streaming there spent 56% of factor time in Python
    # dispatch (BENCH_r03, 0.66x scipy) while compile is cheap; group
    # on accelerators, where the compile of one whole-factor program
    # dominates instead.  (gran itself is resolved above, pre-plan.)
    if MESH is not None:
        # mesh mode routes through the central dispatch so the auto rule
        # (numeric/factor.py) picks the shard_map SPMD tier on a
        # single-process mesh; BENCH_GRANULARITY still names an explicit
        # tier (spmd|stream|mega|fused — "group"/"level" mean stream)
        from superlu_dist_tpu.numeric.factor import get_executor
        ex = get_executor(plan, DTYPE,
                          executor={"group": "stream",
                                    "level": "stream"}.get(gran, gran),
                          mesh=MESH, gemm_prec=GEMM_PREC)
        # spmd: did the row actually run the one-program shard_map tier
        # (granularity "program"), or a GSPMD streamed/mega fallback?
        RESULT["spmd"] = ex.granularity == "program"
        _log(f"mesh executor: {type(ex).__name__} "
             f"(granularity={ex.granularity}, spmd={RESULT['spmd']})")
    elif gran == "mega":
        from superlu_dist_tpu.numeric.mega import MegaExecutor
        ex = MegaExecutor(plan, DTYPE)
    elif gran == "fused":
        from superlu_dist_tpu.numeric.factor import make_factor_fn

        class _Fused:
            offload = "none"
            granularity = "fused"
            n_kernels = 1
            last_dispatch_seconds = None

            def __init__(self):
                from superlu_dist_tpu.symbolic.symbfact import _front_flops
                self._fn = make_factor_fn(plan, DTYPE)
                # the fused path keeps real batch sizes (no pow-2 pad)
                self.executed_flops = float(sum(
                    g.batch * _front_flops(g.w, g.u) for g in plan.groups))

            def __call__(self, avals, thresh):
                return self._fn(avals, thresh)

        ex = _Fused()
    else:
        ex = StreamExecutor(plan, DTYPE, granularity=gran)
    # Crash-consistent warm call (persist/checkpoint.py): checkpoint the
    # compile/warm factorization — the phase the BENCH_r02 n=110592 run
    # died in — so a watchdog kill leaves a durable frontier in the row,
    # and a prior killed run's frontier (plan-fingerprint + value-digest
    # verified) is RESUMED instead of refactoring from zero.  The timed
    # reps below run with checkpointing disarmed: the interval flush
    # blocks the async dispatch stream and would poison the measurement.
    _ckpt = None
    if gran in ("group", "mega") and DTYPE != "bfloat16":
        try:
            from superlu_dist_tpu.persist.checkpoint import (
                FactorCheckpointer, load_checkpoint)
            from superlu_dist_tpu.utils.options import env_int
            _ck_dir = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), ".cache",
                "bench_ckpt", RESULT["metric"])
            try:
                st = load_checkpoint(_ck_dir, plan=plan,
                                     pattern_values=avals_np,
                                     thresh=thresh_np, dtype=DTYPE,
                                     gemm_prec=GEMM_PREC)
                ex.resume = st
                RESULT["resumed_from_groups"] = st.k
                _log(f"resuming factorization from checkpoint frontier "
                     f"{st.k}/{len(plan.groups)} ({_ck_dir})")
            except Exception:
                pass            # no / incompatible checkpoint: fresh run
            _ckpt = FactorCheckpointer(
                _ck_dir, plan, avals_np, thresh_np, DTYPE,
                every=env_int("SLU_TPU_CKPT_EVERY") or 8,
                gemm_prec=GEMM_PREC)
            ex.checkpoint = _ckpt
        except Exception as e:                      # pragma: no cover
            _log(f"checkpoint arming failed: {type(e).__name__}: {e}")
            _ckpt = None
    RESULT["offload"] = ex.offload
    RESULT["granularity"] = ex.granularity
    RESULT["n_kernels"] = ex.n_kernels
    RESULT["executed_flops"] = ex.executed_flops
    RESULT["padding_factor"] = round(ex.executed_flops / plan.flops, 2)
    t_up = time.perf_counter()
    avals = jnp.asarray(avals_np)
    thresh = jnp.asarray(thresh_np)
    if tracer.enabled:
        jax.block_until_ready((avals, thresh))
        tracer.complete("upload-avals", "comm", t_up,
                        time.perf_counter() - t_up, op="h2d",
                        bytes=int(avals_np.nbytes + thresh_np.nbytes))
    out = ex(avals, thresh)
    jax.block_until_ready(out[0])
    _blk = COMPILE_STATS.block(since=_comp0, top=16)
    RESULT["compile_seconds"] = _blk["seconds"]
    RESULT["compile_census"] = _blk["census"]
    RESULT["compile_persistent_hits"] = _blk["persistent_hits"]
    # programs actually built this run (vs n_kernels = the full set)
    RESULT["n_kernels_compiled"] = _blk["builds"]
    # time spent on builds the persistent cache did NOT serve from disk
    # — exactly 0 on a bucket-set warm start (the acceptance field; the
    # plain compile_seconds keeps trace/lower/cache-load overhead)
    RESULT["compile_fresh_seconds"] = _blk["fresh_seconds"]
    # the mega executor AOT-stages, so the exact XLA-compile stage (the
    # part the persistent cache eliminates) is known separately
    _xla = sum(r.compile_seconds or 0.0
               for r in COMPILE_STATS.records[_comp0:])
    if _xla:
        RESULT["xla_compile_seconds"] = round(_xla, 4)
    # program-audit fields (SLU_TPU_VERIFY_PROGRAMS=1, slulint v4): how
    # much of the executors' declared-dead input volume is donated and
    # how many bytes the compiled programs bake as constants — the
    # peak-memory and warm-start honesty axes of the IR-audit tier
    _aud = COMPILE_STATS.audit_block()
    if _aud["programs"]:
        RESULT["programs_audited"] = _aud["programs"]
        RESULT["donation_coverage_pct"] = _aud["donation_coverage_pct"]
        RESULT["baked_const_bytes"] = _aud["baked_const_bytes"]
    # sharding-audit fields (SLU_TPU_VERIFY_SHARDING=1, slulint v6):
    # the worst program's static peak-live-bytes estimate and the
    # gathered/replicated traffic the SLU119 walk priced — the
    # will-it-fit-HBM axes of the sharding tier
    if _aud["programs_sharding_audited"]:
        RESULT["programs_sharding_audited"] = \
            _aud["programs_sharding_audited"]
        RESULT["peak_bytes_est"] = _aud["peak_bytes_est"]
        RESULT["replicated_bytes"] = _aud["replicated_bytes"]
    tracer.complete("factor-compile", "phase", t_phase,
                    time.perf_counter() - t_phase,
                    kernels=ex.n_kernels, offload=ex.offload,
                    compile_seconds=_blk["seconds"])
    _log(f"warm (compile) done, kernels={ex.n_kernels}, "
         f"offload={ex.offload}, compile {_blk['seconds']:.1f}s "
         f"({_blk['builds']} builds, {_blk['persistent_hits']} disk hits)")
    if _ckpt is not None:
        # the warm factorization completed: the frontier is no longer
        # needed (and must not leak into the timed reps)
        ex.checkpoint = None
        _ckpt.complete(cleanup=True)
        _ckpt = None

    _set_phase("factor-time")
    times = []
    mfu_reps = []
    for rep in range(REPS):
        t0 = time.perf_counter()
        out = ex(avals, thresh)
        jax.block_until_ready(out[0])
        dt = time.perf_counter() - t0
        tracer.complete("FACT", "phase", t0, dt, rep=rep)
        times.append(dt)
        # progressive: every rep updates the reported number, so a
        # watchdog fire mid-loop still carries a real measurement; mfu
        # is recorded PER REP (and rounded to 4 decimals — small-but-
        # real CPU utilizations must not print as 0.0) so the perf-
        # regress gate sees precision-tagged per-rep baselines
        mfu_reps.append(round(100.0 * plan.flops / dt / (PEAK_GF * 1e9),
                              4))
        t_dev = min(times)
        RESULT["value"] = round(plan.flops / t_dev / 1e9, 2)
        RESULT["factor_seconds"] = t_dev
        RESULT["mfu_pct"] = round(
            100.0 * plan.flops / t_dev / (PEAK_GF * 1e9), 4)
        RESULT["mfu_pct_reps"] = list(mfu_reps)
        if ex.last_dispatch_seconds is not None:
            RESULT["dispatch_seconds"] = round(ex.last_dispatch_seconds, 4)
        if getattr(ex, "last_offload_wait_seconds", None) is not None:
            RESULT["offload_wait_seconds"] = round(
                ex.last_offload_wait_seconds, 4)
        _log(f"rep {rep}: {dt:.3f}s -> "
             f"{plan.flops / dt / 1e9:.1f} GFLOP/s")
    fronts, tiny = out
    RESULT["tiny_pivots"] = int(tiny)
    # Everything past this point (solve, residual, CPU baseline) must not
    # be able to zero the factor GFLOPS: each phase degrades independently
    # and the JSON line always prints.
    _set_phase("solve-residual")
    t_phase = time.perf_counter()
    try:
        numeric = NumericFactorization(plan=plan, fronts=list(fronts),
                                       tiny_pivots=int(tiny),
                                       dtype=jnp.dtype(DTYPE))
        ones = np.ones(n)
        ident = np.arange(n, dtype=np.int64)
        lu = LUFactorization(n=n, options=Options(), equed="N", dr=ones,
                             dc=ones, r1=ones, c1=ones, row_order=ident,
                             col_order=None, sf=sf, plan=plan,
                             numeric=numeric, a=a, mesh=MESH)
        xt = np.random.default_rng(0).standard_normal(n)
        b = a.matvec(xt)
        x, _ = iterative_refinement(a, b, lu.solve_factored(b),
                                    lu.solve_factored)
        RESULT["residual"] = float(np.linalg.norm(b - a.matvec(x))
                                   / max(np.linalg.norm(b), 1e-300))
        # ||x - xtrue||_inf / ||x||_inf — the pdinf_norm_error metric
        # (EXAMPLE/pddrive.c:235)
        RESULT["xtrue_inf_error"] = float(
            np.max(np.abs(x - xt)) / max(np.max(np.abs(x)), 1e-300))
        # warm solve timing + rate — the reference's solve Mflops line
        # (SRC/util.c:521-529); flops ~ 2*(nnz(L)+nnz(U)) per RHS
        t0 = time.perf_counter()
        lu.solve_factored(b)
        RESULT["solve_seconds"] = round(time.perf_counter() - t0, 5)
        RESULT["solve_gflops"] = round(
            2.0 * (sf.nnz_L + sf.nnz_U)
            / max(RESULT["solve_seconds"], 1e-12) / 1e9, 3)
        solve_path = lu.solve_path     # resolved by the first solve
        if MESH is not None and lu.dev_solver is not None:
            from superlu_dist_tpu.parallel.spmd import SpmdSolver
            if isinstance(lu.dev_solver, SpmdSolver):
                # the mesh row's triangular sweeps ran as shard_map
                # programs (one per sweep bucket), not the host loop
                solve_path = "device-spmd"
        RESULT["solve_path"] = solve_path
        _log(f"residual {RESULT['residual']:.2e} via {solve_path} solve")
    except Exception as e:                       # pragma: no cover
        RESULT["solve_path"] = f"failed: {type(e).__name__}: {e}"
        _log(f"solve phase failed: {e}")

    tracer.complete("solve-residual", "phase", t_phase,
                    time.perf_counter() - t_phase)

    # Serving hot path (ROADMAP item 1): the DEVICE batched solve at a
    # many-RHS sweep — solve_gflops becomes {"1": ..., "64": ...,
    # "1024": ...} (structural flops, honest numerator) plus the
    # solve-plan schedule stats and the nrhs-inclusive padding factor
    # (solve/plan.py).  Each size degrades independently under the
    # remaining watchdog budget; a failure leaves the scalar host
    # numbers from the phase above in place.
    _set_phase("solve-bench")
    t_phase = time.perf_counter()
    try:
        _sizes = [int(s) for s in os.environ.get(
            "BENCH_SOLVE_NRHS", "1,64,1024").split(",") if s.strip()]
        if numeric.on_host:
            # offloaded factors would re-upload per solve — the device
            # solve bench would measure the PCIe link, not the sweeps
            RESULT["solve_bench"] = "skipped: factors host-resident"
        elif _sizes:
            from superlu_dist_tpu.solve.plan import build_solve_plan
            lu.solve_path = "device"
            lu.dev_solver = None
            sp = build_solve_plan(plan)
            RESULT["solve_plan"] = sp.schedule_stats(nrhs=max(_sizes))
            from superlu_dist_tpu.obs.slo import get_accounter
            acct = get_accounter()
            gfl = {}
            secs = {}
            lat50 = {}
            lat99 = {}
            rng = np.random.default_rng(1)
            sflops = 2.0 * (sf.nnz_L + sf.nnz_U)
            for k in _sizes:
                if DEADLINE - (time.perf_counter() - T0) < 180:
                    _log(f"solve-bench: budget low, skipping nrhs={k}+")
                    break
                d = rng.standard_normal((n, k))
                d = d[:, 0] if k == 1 else d
                lu.solve_factored(d)          # warm (compile) call
                # repeated timed solves: min feeds the throughput
                # number (the factor-rep convention), the distribution
                # feeds the latency percentiles the SLO layer and
                # bench_history track
                reps = []
                for _ in range(8):
                    t0 = time.perf_counter()
                    lu.solve_factored(d)
                    reps.append(time.perf_counter() - t0)
                    acct.observe(k, reps[-1], klass="bench")
                    if DEADLINE - (time.perf_counter() - T0) < 150:
                        break
                dt = min(reps)
                reps_ms = np.asarray(reps) * 1e3
                secs[str(k)] = round(dt, 5)
                gfl[str(k)] = round(sflops * k / max(dt, 1e-12) / 1e9, 3)
                lat50[str(k)] = round(float(np.percentile(reps_ms, 50)), 4)
                lat99[str(k)] = round(float(np.percentile(reps_ms, 99)), 4)
                _log(f"solve nrhs={k}: {dt:.4f}s -> "
                     f"{gfl[str(k)]} GFLOP/s (device), "
                     f"p50 {lat50[str(k)]} ms over {len(reps)} reps")
                # progressive, like the factor reps: a watchdog fire
                # mid-sweep still carries the sizes measured so far
                RESULT["solve_gflops"] = dict(gfl)
                RESULT["solve_seconds_nrhs"] = dict(secs)
                RESULT["latency_p50_ms"] = dict(lat50)
                RESULT["latency_p99_ms"] = dict(lat99)
                RESULT["solve_path"] = "device"
                if MESH is not None and lu.dev_solver is not None:
                    from superlu_dist_tpu.parallel.spmd import SpmdSolver
                    if isinstance(lu.dev_solver, SpmdSolver):
                        RESULT["solve_path"] = "device-spmd"
                if lu.dev_solver is not None \
                        and lu.dev_solver.last_solve_stats:
                    RESULT["solve_padding_factor"] = \
                        lu.dev_solver.last_solve_stats["padding_factor"]
    except Exception as e:                       # pragma: no cover
        RESULT["solve_bench"] = f"failed: {type(e).__name__}: {e}"
        _log(f"solve-bench phase failed: {e}")

    tracer.complete("solve-bench", "phase", t_phase,
                    time.perf_counter() - t_phase)

    # Baseline: serial SuperLU (same code family as the reference) with
    # host CPU BLAS, factoring the identical matrix
    _set_phase("cpu-baseline")
    t_phase = time.perf_counter()
    try:
        import scipy.sparse as sp
        from scipy.sparse.linalg import splu
        A = sp.csr_matrix((a.data, a.indices, a.indptr),
                          shape=(n, n)).tocsc()
        t0 = time.perf_counter()
        splu(A)
        t_cpu = time.perf_counter() - t0
        RESULT["baseline_seconds"] = t_cpu
        RESULT["baseline"] = ("scipy.splu (serial SuperLU, f64, host BLAS),"
                              " same matrix")
        RESULT["vs_baseline"] = round(t_cpu / RESULT["factor_seconds"], 2)
        _log(f"scipy splu baseline {t_cpu:.2f}s -> "
             f"vs_baseline {RESULT['vs_baseline']}x")
    except Exception as e:                        # pragma: no cover
        _log(f"baseline failed: {e}")

    tracer.complete("cpu-baseline", "phase", t_phase,
                    time.perf_counter() - t_phase)
    _set_phase("done")
    # flush explicitly: the watchdog's os._exit skips atexit, so the
    # artifact must be on disk before the final line prints
    tracer.close()
    _emit(final=True)


if __name__ == "__main__":
    try:
        main()
    except BaseException as e:           # the ONE-JSON-line contract holds
        RESULT.setdefault("error", f"{type(e).__name__}: {e}")
        _emit(final=True)
        raise

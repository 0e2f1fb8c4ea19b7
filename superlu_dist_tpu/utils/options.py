"""Solver options.

Mirrors the reference's runtime option struct ``superlu_dist_options_t``
(SRC/superlu_defs.h:628-657) and its defaults ``set_default_options_dist``
(SRC/util.c:376-401), re-expressed for the TPU-native pipeline.  TPU-specific
knobs (factor dtype, bucket geometry) replace the CPU/GPU tuning env vars
(sp_ienv_dist, SRC/sp_ienv.c:70-123; get_cublas_nb etc., SRC/util.c:932-972).
"""

from __future__ import annotations

import dataclasses
import enum
import os


class YesNo(enum.Enum):
    NO = 0
    YES = 1


# ---------------------------------------------------------------------------
# Environment-knob registry — the single source of truth for every env var
# the project reads (the sp_ienv_dist environment tier generalized,
# SRC/sp_ienv.c:70-123).  Every read routes through env_int/env_float/
# env_str/env_flag below, so slulint rule SLU104 (analysis/rules_env.py)
# can flag any os.environ read whose key is not declared here, and
# SLU_TPU_STRICT_ENV=1 turns a typo'd SLU_TPU_* knob name into a hard
# error instead of a silently-ignored setting.  docs/ANALYSIS.md carries
# the generated table (knob_table_md).
# ---------------------------------------------------------------------------


class UnknownKnobError(KeyError):
    """An env knob was read or set that the registry does not declare."""


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    kind: str            # "int" | "float" | "str" | "flag"
    default: object
    help: str
    group: str = "solver"
    choices: tuple | None = None


KNOB_REGISTRY: dict[str, Knob] = {}


def register_knob(name: str, kind: str, default, help: str,
                  group: str = "solver", choices: tuple | None = None) -> None:
    assert kind in ("int", "float", "str", "flag"), kind
    KNOB_REGISTRY[name] = Knob(name, kind, default, help, group, choices)


def _register_all() -> None:
    r = register_knob
    # --- symbolic / blocking (sp_ienv analogs) -----------------------------
    r("NREL", "int", 20, "leaf-subtree relaxation width (reference sp_ienv(2))")
    r("NSUP", "int", 256, "max supernode width (reference sp_ienv(3))")
    r("SLU_TPU_MIN_BUCKET", "int", 8,
      "smallest padded front dimension for size-class bucketing")
    r("SLU_TPU_AMALG_TOL", "float", 1.2,
      "fill-tolerant amalgamation flop-growth tolerance (0 disables)")
    r("SLU_TPU_SYMB_THREADS", "int", 1,
      "threads for the native symbolic factorization (psymbfact analog)")
    # --- numeric executors -------------------------------------------------
    r("SLU_TPU_PRECISION", "str", "highest",
      "MXU pass count for f32 Schur GEMMs (legacy; superseded by "
      "SLU_TPU_GEMM_PREC — an explicitly-set value still maps onto the "
      "tier ladder: default->default, high->f32, highest->highest)",
      group="numeric", choices=("default", "high", "highest"))
    r("SLU_TPU_GEMM_PREC", "str", "",
      "Schur-update GEMM precision tier for the factor hot path "
      "(ops/dense.gemm_precision): bf16 = bf16 inputs with f32 "
      "accumulation (native MXU rate), default = single-pass bf16 on "
      "native inputs (the tensorfloat analog), f32 = 3-pass "
      "(~f32-mantissa), highest = 6-pass full f32.  Empty = 'default' "
      "unless a legacy SLU_TPU_PRECISION is explicitly set.  Reduced "
      "tiers are BERR-gated: the escalation ladder refactors the same "
      "skeleton at the next tier when delivered accuracy misses the "
      "gate (docs/PERFORMANCE.md throughput ladder)", group="numeric",
      choices=("", "bf16", "default", "f32", "highest"))
    r("SLU_TPU_PIVOT_KERNEL", "str", "blocked",
      "panel factorization kernel", group="numeric",
      choices=("blocked", "recursive"))
    r("SLU_TPU_FRONT_BYTES_LIMIT", "float", 6e9,
      "padded-front bytes above which the stream executor offloads to host",
      group="numeric")
    r("SLU_TPU_OFFLOAD_LAG", "int", 8,
      "in-flight group window of the host-offload pipeline", group="numeric")
    r("SLU_TPU_HOST_FLOPS", "float", 0.0,
      "run leading levels below this flop count on the host CPU (0=off)",
      group="numeric")
    r("SLU_TPU_SCHEDULE", "str", "dataflow",
      "factor-group scheduler: earliest-ready dataflow batching or "
      "strict elimination-level lockstep", group="numeric",
      choices=("dataflow", "level"))
    r("SLU_TPU_SCHED_WINDOW", "int", 8,
      "dataflow look-ahead window in elimination levels (1 degenerates "
      "to the level partition, 0 = unbounded)", group="numeric")
    r("SLU_TPU_SCHED_ALIGN", "float", 1.1,
      "shape-key coalescing flop tolerance for batch packing "
      "(<= 1 disables)", group="numeric")
    # --- bucket-ladder closure / mega executor (numeric/{plan,mega}.py) -----
    r("SLU_TPU_BUCKET_BASE", "int", 8,
      "smallest rung of the canonical bucket ladder shared by the plan "
      "bucketing and every executor's pad-to-rung rounding "
      "(numeric/plan.bucket_rung — the one source of truth)",
      group="numeric")
    r("SLU_TPU_BUCKET_GROWTH", "float", 2.0,
      "geometric growth of the canonical bucket ladder (rungs rounded "
      "to multiples of 8 above the base)", group="numeric")
    r("SLU_TPU_BUCKET_CLOSED", "flag", False,
      "close the factor plan's shape-key set: merge every (W, U) "
      "dispatch key onto <= SLU_TPU_BUCKET_KEYS canonical ladder rungs "
      "so the compiled-program count is independent of matrix size "
      "(the mega-executor prerequisite)", group="numeric")
    r("SLU_TPU_BUCKET_KEYS", "int", 6,
      "maximum distinct (W, U) shape keys a closed plan may carry "
      "(SLU_TPU_BUCKET_CLOSED=1); the mega executor compiles exactly "
      "one program per key", group="numeric")
    r("SLU_TPU_EXECUTOR", "str", "auto",
      "numeric-factorization executor: one whole-program jit (fused), "
      "one kernel per shape key (stream), one data-driven program per "
      "closed shape bucket (mega), the shard_map mesh tier with "
      "in-program collectives (spmd — needs a single-process mesh), or "
      "the backend-dependent default (auto).  df64 factorization keeps "
      "its own executor",
      group="numeric", choices=("auto", "fused", "stream", "mega",
                                "spmd"))
    r("SLU_TPU_SPMD", "str", "auto",
      "shard_map SPMD tier gate (parallel/spmd.py): auto/empty = on "
      "for single-process meshes (one compiled program per factor group and "
      "per solve-sweep bucket, bitwise-identical to the lockstep "
      "path), 0/off = keep the GSPMD stream/fused tiers, anything "
      "else = force on", group="numeric",
      choices=("auto", "0", "1", "on", "off"))
    r("SLU_TPU_DIAG_INV", "flag", False,
      "precompute inverted diagonal blocks (reference DiagInv)",
      group="numeric")
    # --- device solve / serving tier (solve/plan.py, serve/) ---------------
    r("SLU_TPU_SOLVE_SCHEDULE", "str", "dataflow",
      "sweep-batch scheduler for the device triangular solve: "
      "earliest-ready dataflow batching, strict level lockstep, or the "
      "factor plan's grouping 1:1", group="solve",
      choices=("dataflow", "level", "factor"))
    r("SLU_TPU_SOLVE_WINDOW", "int", 0,
      "dataflow look-ahead window of the solve scheduler in elimination "
      "levels (0 = unbounded — the solve holds no Schur pool, so "
      "liveness does not bound it; 1 degenerates to the level partition)",
      group="solve")
    r("SLU_TPU_SOLVE_ALIGN", "float", 1.25,
      "solve-side shape-key coalescing flop tolerance, applied on top "
      "of the factor keys (<= 1 disables; promoted members get "
      "identity/zero panel padding)", group="solve")
    r("SLU_TPU_SOLVE_NRHS_MAX", "int", 1024,
      "largest nrhs bucket — the column-chunking cap that closes the "
      "solve-kernel compile set", group="solve")
    r("SLU_TPU_SOLVE_NRHS_GROWTH", "float", 1.5,
      "geometric nrhs bucket growth past the power-of-two rungs "
      "(rounded to multiples of 32)", group="solve")
    r("SLU_TPU_SOLVE_TRSM_LEAF", "int", 64,
      "recursive blocked-TRSM leaf width for supernode diagonal blocks "
      "(0 = unblocked vmapped triangular solves)", group="solve")
    r("SLU_TPU_SERVE_MAX_BATCH", "int", 0,
      "SolveServer micro-batch column cap (0 = the nrhs bucket cap)",
      group="serve")
    r("SLU_TPU_SERVE_MAX_WAIT_MS", "float", 2.0,
      "SolveServer coalescing window: how long the dispatcher holds the "
      "oldest pending request open for co-batching before dispatching",
      group="serve")
    r("SLU_TPU_SERVE_QUEUE_MAX", "int", 0,
      "SolveServer admission cap in pending COLUMNS: a submit that "
      "would exceed it is shed with ServeOverloadError instead of "
      "queueing (0 = unbounded, the legacy behavior)", group="serve")
    r("SLU_TPU_SERVE_DEADLINE_MS", "float", 0.0,
      "per-request serving deadline: columns still queued past it are "
      "expired with ServeDeadlineError and removed from the queue "
      "(0 = off)", group="serve")
    r("SLU_TPU_SERVE_BERR_MAX", "float", 0.0,
      "per-request componentwise-berr quality gate: a served ticket "
      "whose berr exceeds it is routed through a per-ticket iterative-"
      "refinement rung (refine/ir.refine_ticket) before delivery "
      "(0 = off; needs the original matrix on the handle)",
      group="serve")
    r("SLU_TPU_SERVE_SCRUB_S", "float", 0.0,
      "factor-integrity scrub period: a background thread re-hashes "
      "the handle's panel stacks against their persist-bundle sha256 "
      "digests every this-many seconds, quarantining the handle with "
      "FactorCorruptError on mismatch (0 = off)", group="serve")
    # --- serving fleet -----------------------------------------------------
    r("SLU_TPU_FLEET_REPLICAS", "int", 2,
      "FleetRouter default replica count (serve/fleet.py): how many "
      "SolveServer replicas the routing front fans submits across",
      group="fleet")
    r("SLU_TPU_FLEET_KIND", "str", "thread",
      "fleet replica isolation: in-process worker threads or spawned "
      "worker processes behind the same interface", group="fleet",
      choices=("thread", "process"))
    r("SLU_TPU_FLEET_HANDLE_BYTES", "int", 0,
      "per-replica resident-handle byte budget for the multi-handle "
      "LRU cache (serve/handlecache.py, sized via the persist lu_meta "
      "cheap peek): least-recently-used idle handles are evicted and "
      "scrub-verified on reload (0 = unbounded)", group="fleet")
    r("SLU_TPU_FLEET_QUEUE_MAX", "int", 0,
      "fleet-level admission cap in undelivered COLUMNS across all "
      "replicas: a submit past it is shed with ServeOverloadError "
      "(reason fleet_queue_full) at the router, before any replica "
      "queues it (0 = unbounded)", group="fleet")
    r("SLU_TPU_FLEET_DEADLINE_MS", "float", 0.0,
      "end-to-end per-ticket fleet deadline: a ticket undelivered past "
      "it — queued, in flight, or mid-failover — is expired with "
      "ServeDeadlineError by the health monitor or the waiting ticket "
      "itself (0 = off)", group="fleet")
    r("SLU_TPU_FLEET_HEALTH_S", "float", 0.05,
      "fleet health-monitor poll period: replica process/thread "
      "liveness (pid_alive — the PR 8 detector verdict), failover "
      "re-routing of undelivered tickets, and deadline sweeps run on "
      "this cadence", group="fleet")
    r("SLU_TPU_POOL_PARTITION", "flag", False,
      "shard the Schur update pool across all mesh devices", group="numeric")
    # --- distributed tier --------------------------------------------------
    r("SLU_TPU_PAR_SYMB_FACT", "flag", False,
      "partition ordering+symbolic across ranks (ParSymbFact analog)",
      group="parallel")
    r("SLU_TPU_FAULTS", "str", "",
      "fault-injection spec for TreeComm (e.g. 'drop=0.2,seed=7')",
      group="parallel")
    r("SLU_TPU_VERIFY_COLLECTIVES", "flag", False,
      "TreeComm lockstep-verify mode: cross-check every collective's "
      "(call-site, op, shape/dtype, seq) digest across ranks and raise "
      "CollectiveMismatchError instead of deadlocking (runtime SLU106)",
      group="parallel")
    r("SLU_TPU_VERIFY_PROGRAMS", "flag", False,
      "program-audit mode (utils/programaudit.py): every jitted "
      "program the executors build is traced once at construction/"
      "AOT-stage time and walked against the slulint v4 IR rules — "
      "SLU111 donation/aliasing, SLU112 baked-constant blowup, SLU114 "
      "SPMD collective lockstep — raising ProgramAuditError before the "
      "program runs; feeds slu_program_audit_total and the compile "
      "census's donation-coverage / baked-const-bytes fields",
      group="parallel")
    r("SLU_TPU_VERIFY_DTYPES", "flag", False,
      "precision-audit mode (utils/programaudit.py): every jitted "
      "program the executors build is additionally walked against the "
      "slulint v5 precision rules — SLU115 narrowing converts outside "
      "the sanctioned GEMM-input pattern, SLU116 dot_general "
      "accumulation width below the widest operand (or below f32 on "
      "16-bit inputs) — raising PrecisionAuditError before the program "
      "runs; feeds slu_precision_audit_total and `label#dtypes` census "
      "audit notes.  Independent of SLU_TPU_VERIFY_PROGRAMS",
      group="parallel")
    r("SLU_TPU_VERIFY_SHARDING", "flag", False,
      "sharding-audit mode (utils/programaudit.py): every jitted "
      "program the executors build is additionally walked against the "
      "slulint v6 sharding/memory rules — SLU119 implicit replication/"
      "reshard blowup (an op whose operand shardings force an implicit "
      "all-gather or a >= 1 MiB reshard), SLU121 static peak-live-bytes "
      "against SLU_TPU_MEM_BUDGET_BYTES — raising ShardingAuditError/"
      "MemoryBudgetError before the program runs; feeds "
      "slu_sharding_audit_total and `label#sharding` census audit notes "
      "(peak_bytes_est, replicated_bytes).  Independent of "
      "SLU_TPU_VERIFY_PROGRAMS/SLU_TPU_VERIFY_DTYPES", group="parallel")
    r("SLU_TPU_MEM_BUDGET_BYTES", "int", 0,
      "per-program static peak-memory budget in bytes (0 = off): the "
      "SLU121 liveness walk's high-water live-byte estimate (args + "
      "consts + intermediates, free-after-last-use) must fit it or the "
      "submit raises MemoryBudgetError naming the program — the mega "
      "executor's padded-rung bucket programs are the first real "
      "consumer (the error names the offending bucket rung).  Setting "
      "it implies the sharding audit even without "
      "SLU_TPU_VERIFY_SHARDING=1", group="parallel")
    r("SLU_TPU_VERIFY_LOCKS", "flag", False,
      "lock-order verify mode (utils/lockwatch.py): instrument every "
      "make_lock/make_condition lock, record per-thread acquisition "
      "stacks into a global order graph, and raise LockOrderError "
      "naming both call sites on the first inversion instead of "
      "deadlocking (runtime SLU109); feeds the slu_lock_hold_seconds "
      "histogram when metrics are on", group="parallel")
    # --- rank-failure tolerance (parallel/recover.py, docs/RELIABILITY.md) --
    r("SLU_TPU_COMM_TIMEOUT_S", "float", 0.0,
      "bounded-wait collectives: every native tree leg's spin loop gets "
      "this deadline (exponential backoff + jitter); on expiry the "
      "failure detector is consulted — dead peer => RankFailureError on "
      "every survivor, live peer => retry.  0 = unbounded (legacy)",
      group="parallel")
    r("SLU_TPU_COMM_RETRIES", "int", 0,
      "timed-out-but-peer-alive retry budget per collective leg; "
      "exhausting it raises CommTimeoutError.  0 = unlimited (a slow "
      "peer is waited out; only DEATH fails the collective)",
      group="parallel")
    r("SLU_TPU_HEARTBEAT_S", "float", 0.5,
      "failure-detector heartbeat interval (epoch bumps in the shared "
      "segment + heartbeat-age gauge); the thread only starts when "
      "SLU_TPU_COMM_TIMEOUT_S > 0.  0 disables the thread (pid "
      "liveness still detects death)", group="parallel")
    r("SLU_TPU_FT", "str", "abort",
      "rank-failure policy for fault-tolerant drivers "
      "(parallel/recover.pgssvx_ft): abort = raise RankFailureError; "
      "shrink = survivors re-partition and resume from the checkpoint "
      "frontier; respawn = replacement processes take the dead ranks",
      group="parallel", choices=("abort", "shrink", "respawn"))
    # --- index width -------------------------------------------------------
    r("SLU_TPU_INT64", "flag", False,
      "64-bit pattern indices (reference XSDK_INDEX_SIZE=64 analog)")
    # --- solver health & recovery ------------------------------------------
    r("SLU_TPU_RECOVERY", "flag", True,
      "automatic escalation ladder on refinement stagnation",
      group="recovery")
    r("SLU_TPU_SENTINELS", "flag", True,
      "non-finite isfinite sentinels in the numeric layer", group="recovery")
    r("SLU_TPU_REFACTOR_BERR_MAX", "float", 0.0,
      "componentwise-BERR adoption gate for refactor(handle, new_values): "
      "the shadow factorization's canary solve must come in at or under "
      "this backward error or the refactor rolls back (0 = finite-only "
      "gate; an explicit berr_max argument overrides)", group="recovery")
    r("SLU_TPU_REFACTOR_ESCALATE", "flag", True,
      "let a BERR-gated refactor climb the GEMM-precision ladder "
      "(ops/dense.next_gemm_precision, up to recovery.max_rungs shadow "
      "attempts) before rolling back; off = single attempt at the "
      "handle's tier", group="recovery")
    # --- persistence / crash consistency -----------------------------------
    r("SLU_TPU_CKPT_EVERY", "int", 0,
      "flush a factor checkpoint every K completed dispatch groups "
      "(0 = interval checkpoints off; breakdown/deadline/SIGTERM "
      "flushes stay armed once a checkpointer exists)", group="persist")
    r("SLU_TPU_CKPT_DIR", "str", "",
      "factor-checkpoint bundle directory (default .slu_ckpt in the "
      "working directory)", group="persist")
    r("SLU_TPU_DEADLINE_S", "float", 0.0,
      "cooperative factorization deadline in seconds (0 = off): checked "
      "between dispatch groups, checkpoint flushed first, raises "
      "DeadlineExceededError — collectively on the multi-rank path",
      group="persist")
    r("SLU_TPU_DEADLINE_POLL", "int", 1,
      "poll cadence of the collective deadline flag allreduce "
      "(one exchange per N dispatch groups)", group="persist")
    # --- observability -----------------------------------------------------
    r("SLU_TPU_TRACE", "str", "",
      "structured span trace output path ('%p' expands to the pid)",
      group="obs")
    r("SLU_TPU_STATS", "flag", False,
      "print the PStatPrint-analog report from any driver run", group="obs")
    r("SLU_TPU_PROGRESS", "int", 0,
      "log every K groups/levels issued (0=silent)", group="obs")
    r("SLU_TPU_PEAK_GFLOPS", "float", 0.0,
      "peak GFLOP/s override for the MFU denominator (bench.py, "
      "scripts/mfu_report.py); 0 = auto-detect from the per-backend/"
      "per-precision peak table (utils/peaks.py — TPU kinds tabulated, "
      "CPU calibrated with a one-shot micro-GEMM)", group="obs")
    r("SLU_TPU_METRICS", "str", "",
      "metrics registry: '1' enables; a path additionally dumps the "
      "JSON/Prometheus export there at exit ('%p' expands to the pid)",
      group="obs")
    r("SLU_TPU_FLIGHTREC", "str", "",
      "flight recorder: '1' enables (default flightrec-%p.json dump); "
      "a path names the postmortem artifact ('%p' expands to the pid)",
      group="obs")
    r("SLU_TPU_FLIGHTREC_DEPTH", "int", 512,
      "flight-recorder ring depth (events kept for the postmortem)",
      group="obs")
    r("SLU_TPU_SLO_P99_MS", "float", 0.0,
      "global p99 latency SLO target in ms for the serving tier "
      "(obs/slo.py SLOEvaluator, fleet health model; 0 = no SLO)",
      group="obs")
    r("SLU_TPU_SLO_TARGETS", "str", "",
      "per-traffic-class p99 SLO overrides, 'class=ms,class=ms' "
      "(classes: serve, fleet, driver, bench; overrides "
      "SLU_TPU_SLO_P99_MS for the named class)", group="obs")
    r("SLU_TPU_SLO_BUDGET", "float", 0.01,
      "SLO error budget: provisioned fraction of requests allowed over "
      "the p99 target; burn rate = over-target fraction / budget",
      group="obs")
    # --- native layer ------------------------------------------------------
    r("SLU_TPU_NO_NATIVE", "flag", False,
      "disable the native C++ host-analysis library", group="native")
    r("SLU_TPU_ND_THREADS", "int", 1,
      "threads for native nested dissection", group="native")
    # --- env discipline ----------------------------------------------------
    r("SLU_TPU_STRICT_ENV", "flag", False,
      "raise on SLU_TPU_* env vars the registry does not declare")
    # --- test / CI harness -------------------------------------------------
    r("SLU_TPU_CHAOS", "str", "",
      "failure-domain chaos-injection spec (testing/chaos.py, e.g. "
      "'kill_group=5', 'nan_supernode=3', 'kill_refactor@step=0', "
      "'poison_values=2'); empty = off", group="test")
    r("SLU_TPU_DRYRUN_BIG", "str", "1",
      "__graft_entry__: include the n=1e5 pool-partition dryrun phase",
      group="test")
    # --- external (read, not owned, by this project) -----------------------
    for name, help_ in (
            ("JAX_PLATFORMS", "jax backend selection"),
            ("XLA_FLAGS", "XLA compiler/runtime flags"),
            ("LIBTPU_INIT_ARGS", "TPU runtime/compiler flags (the package "
             "appends its fiber stack size on import)"),
            ("JAX_ENABLE_X64", "jax 64-bit mode"),
            ("JAX_DEBUG_NANS", "raise on NaN production in jitted code"),
            ("PYTHONPATH", "module search path for subprocesses")):
        r(name, "str", "", help_, group="external")
    # --- bench.py ----------------------------------------------------------
    r("BENCH_DEADLINE_S", "float", 1350.0,
      "bench watchdog deadline (seconds)", group="bench")
    for name, kind, default, help_ in (
            ("BENCH_NX", "int", 48, "Poisson grid edge (n = NX^3)"),
            ("BENCH_REPS", "int", 3, "timed repetitions"),
            ("BENCH_DTYPE", "str", "float32", "factor dtype"),
            ("BENCH_PEAK_F32_TFLOPS", "float", 49.0,
             "peak f32 TFLOP/s for the MFU denominator"),
            ("BENCH_RELAX", "int", None, "NREL override for the bench"),
            ("BENCH_MAXSUPER", "int", None, "NSUP override for the bench"),
            ("BENCH_MINBUCKET", "int", None, "min bucket override"),
            ("BENCH_GROWTH", "float", None, "bucket growth override"),
            ("BENCH_AMALG", "float", None, "amalgamation tol override"),
            ("BENCH_MATRIX", "str", "poisson3d", "bench matrix family"),
            ("BENCH_GRANULARITY", "str", None, "stream granularity"),
            ("BENCH_SOLVE_NRHS", "str", "1,64,1024",
             "device-solve bench nrhs sweep (comma list; empty skips)"),
            ("BENCH_MESH", "str", "",
             "mesh mode: a 'RxC' spec (e.g. 1x8) factors and solves on "
             "that virtual/real device grid through the shard_map SPMD "
             "tier and emits mesh_shape/n_devices/spmd row fields; "
             "empty = single-device bench")):
        r(name, kind, default, help_, group="bench")
    # --- measurement scripts ----------------------------------------------
    for name, kind, default, help_ in (
            ("CONFIG4_MESH", "str", "1", "config4_virtual mesh spec"),
            ("CONFIG4_NX", "int", 100, "config4_virtual grid edge"),
            ("CONFIG4_DTYPE", "str", "float32", "config4_virtual dtype"),
            ("PGS_NX", "int", 48, "pgssvx_scale grid edge"),
            ("MAS_DEADLINE_S", "float", 14400.0,
             "mesh_analysis_scale deadline"),
            ("MAS_NX", "int", 48, "mesh_analysis_scale grid edge"),
            ("MAS_MODES", "str", "replicated,root_bcast,parsymb",
             "mesh_analysis_scale mode list"),
            ("DF64_NX", "str", "12,16,20", "df64_cost_tpu grid edges"),
            ("DF64S_MESH", "str", "1", "df64_scale mesh spec"),
            ("DF64S_NX", "int", 16, "df64_scale grid edge"),
            ("DF64S_KAPPA", "float", 1e10, "df64_scale condition target"),
            ("DF64S_COMPLEX", "str", "0", "df64_scale complex twin"),
            ("SLU_TPU_BENCH_HISTORY", "str", "",
             "bench-history JSONL DB path (default .cache/"
             "bench_history.jsonl; scripts/bench_history.py + "
             "check_perf_regress.py)"),
            ("PERF_GATE_NX", "int", 8,
             "check_perf_regress micro-bench grid edge"),
            ("PERF_GATE_TOL", "float", 0.5,
             "check_perf_regress noise tolerance (fail below "
             "(1-tol)*median)"),
            ("PERF_GATE_MIN_SAMPLES", "int", 3,
             "check_perf_regress history rows required before enforcing"),
            ("SLO_GATE_NRHS", "str", "1,8",
             "check_slo served-workload nrhs sweep (comma list)"),
            ("SLO_GATE_REQUESTS", "int", 48,
             "check_slo requests per nrhs bucket"),
            ("SLO_GATE_TOL", "float", 1.0,
             "check_slo noise tolerance (fail above (1+tol)*median p99)"),
            ("SLO_GATE_MIN_SAMPLES", "int", 3,
             "check_slo history rows required before enforcing")):
        r(name, kind, default, help_, group="scripts")


_register_all()

_FLAG_FALSE = ("", "0", "false", "no", "off")
_strict_checked = False


def _check_strict_env() -> None:
    """Under SLU_TPU_STRICT_ENV=1, an SLU_TPU_* env var the registry does
    not declare raises (with a did-you-mean) instead of being silently
    ignored — a typo'd knob name can otherwise invalidate a whole
    hardware sweep.  Checked once, on the first registry read."""
    global _strict_checked
    if _strict_checked:
        return
    _strict_checked = True
    raw = os.environ.get("SLU_TPU_STRICT_ENV", "")
    if raw.strip().lower() in _FLAG_FALSE:
        return
    unknown = sorted(k for k in os.environ
                     if k.startswith("SLU_TPU_") and k not in KNOB_REGISTRY)
    if unknown:
        import difflib
        hints = []
        for k in unknown:
            close = difflib.get_close_matches(k, KNOB_REGISTRY, n=1)
            hints.append(f"{k}" + (f" (did you mean {close[0]}?)"
                                   if close else ""))
        raise UnknownKnobError(
            "unknown SLU_TPU_* environment knob(s) under "
            f"SLU_TPU_STRICT_ENV=1: {', '.join(hints)}")


_UNSET = object()


def _knob_raw(name: str, default):
    if name not in KNOB_REGISTRY:
        raise UnknownKnobError(
            f"env knob {name!r} is not declared in the registry "
            "(superlu_dist_tpu/utils/options.py) — register it there")
    _check_strict_env()
    raw = os.environ.get(name)
    d = KNOB_REGISTRY[name].default if default is _UNSET else default
    return raw, d


def env_int(name: str, default=_UNSET) -> int:
    """Registered integer knob; unset or unparsable values yield the
    default (the historical _env_int contract)."""
    raw, d = _knob_raw(name, default)
    if raw is None:
        return d
    try:
        return int(raw)
    except ValueError:
        return d


def env_float(name: str, default=_UNSET) -> float:
    raw, d = _knob_raw(name, default)
    if raw is None:
        return d
    try:
        return float(raw)
    except ValueError:
        return d


def env_str(name: str, default=_UNSET) -> str:
    raw, d = _knob_raw(name, default)
    return d if raw is None else raw


def env_flag(name: str, default=_UNSET) -> bool:
    """Registered on/off knob: unset -> default; '', '0', 'false', 'no',
    'off' (any case) -> False; anything else -> True."""
    raw, d = _knob_raw(name, default)
    if raw is None:
        return bool(d)
    return raw.strip().lower() not in _FLAG_FALSE


def knob_table_md(groups: tuple | None = None) -> str:
    """Markdown table of the registry (docs/ANALYSIS.md carries it; the
    doc test asserts it stays in sync with the registry)."""
    lines = ["| Knob | Kind | Default | Group | Meaning |",
             "|---|---|---|---|---|"]
    for k in sorted(KNOB_REGISTRY.values(),
                    key=lambda k: (k.group, k.name)):
        if groups is not None and k.group not in groups:
            continue
        extra = (f" ({'/'.join(map(str, k.choices))})" if k.choices else "")
        lines.append(f"| `{k.name}` | {k.kind} | `{k.default}` | {k.group} "
                     f"| {k.help}{extra} |")
    return "\n".join(lines)


class Fact(enum.Enum):
    """Factorization reuse tiers (reference fact_t, superlu_defs.h:489-510).

    These are the reference API's main performance feature for time-stepping
    users (SURVEY.md §5 checkpoint/resume): each tier skips more of the
    pipeline on a repeated solve.
    """

    DOFACT = 0                      # factor from scratch
    SamePattern = 1                 # reuse column perm + symbolic + plan
    SamePattern_SameRowPerm = 2     # additionally reuse row perm + scalings
    FACTORED = 3                    # reuse the numeric factors (solve only)


class ColPerm(enum.Enum):
    """Fill-reducing column orderings (reference colperm_t; dispatch
    get_perm_c_dist, SRC/get_perm_c.c:463-530)."""

    NATURAL = 0
    MMD_AT_PLUS_A = 1       # minimum degree on pattern of A^T + A
    ND_AT_PLUS_A = 2        # multilevel nested dissection (METIS analog)
    METIS_AT_PLUS_A = 2     # alias: the reference default maps to our ND
    MY_PERMC = 3            # user-supplied permutation
    MMD_ATA = 4             # minimum degree on pattern of A^T A
    COLAMD = 5              # approximate column MD directly on A


class RowPerm(enum.Enum):
    """Numerical row pivoting strategy (reference rowperm_t;
    dldperm_dist, SRC/dldperm_dist.c:95)."""

    NOROWPERM = 0
    LargeDiag_MC64 = 1      # maximum-product weighted bipartite matching
    LargeDiag_AWPM = 2      # approximate-weight perfect matching (the
                            # CombBLAS HWPM analog — perm only, no scalings)
    MY_PERMR = 3


class IterRefine(enum.Enum):
    """Iterative refinement (reference IterRefine_t; pdgsrfs.c:120)."""

    NOREFINE = 0
    SLU_SINGLE = 1
    SLU_DOUBLE = 2


class Trans(enum.Enum):
    NOTRANS = 0
    TRANS = 1
    CONJ = 2


@dataclasses.dataclass
class RecoveryPolicy:
    """Solver health & recovery policy — the pdgscon/pdgsrfs repair loop
    made automatic (PAPER.md L4/L8: GESP trades pivoting stability for
    speed, then detects and repairs the damage afterwards).

    ``enabled`` drives the escalation ladder in drivers/gssvx.py: when
    iterative refinement stagnates above ``berr_target`` the driver
    escalates residual precision, retries the correction solves on
    higher-precision factors (f64 on CPU, emulated-double df64 on f32-only
    hardware), and finally refactors with diagnostics-informed re-scaling /
    re-ordering.  Every rung is recorded in the SolveReport
    (utils/stats.py) so callers see what degraded and why the answer is
    still trustworthy.

    ``sentinels`` arms the cheap isfinite reductions on factored panels
    (numeric/factor.py, numeric/stream.py) that trip NumericBreakdownError
    at the offending supernode, and the final solution check in the driver.

    ``condest`` selects when the Hager–Higham condition estimate (rcond,
    the pdgscon analog) and the normwise forward-error bound (ferr) are
    computed: "always", "never", or "auto" (only when the ladder fired or
    tiny pivots were replaced — the cases where the answer needs defending).
    """

    enabled: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_RECOVERY"))
    sentinels: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_SENTINELS"))
    condest: str = "auto"              # "always" | "auto" | "never"
    berr_target: float | None = None   # None => 10·eps(residual dtype)
    max_rungs: int = 3                 # ladder depth cap


def _env_int(name: str, default: int) -> int:
    """Back-compat alias for env_int (the knob must be registered)."""
    return env_int(name, default)


def _env_float(name: str, default: float) -> float:
    """Back-compat alias for env_float (the knob must be registered)."""
    return env_float(name, default)


@dataclasses.dataclass
class Options:
    """Runtime options (analog of superlu_dist_options_t).

    Defaults follow set_default_options_dist (SRC/util.c:376-401):
    Fact=DOFACT, Equil=YES, ColPerm=METIS_AT_PLUS_A, RowPerm=LargeDiag_MC64,
    ReplaceTinyPivot, IterRefine=DOUBLE, PrintStat=YES.  The blocking knobs
    read the sp_ienv environment tier (SRC/sp_ienv.c:70-123) at
    construction: NREL (relax), NSUP (max supernode),
    SLU_TPU_MIN_BUCKET — so `NSUP=99 python -m superlu_dist_tpu ...`
    behaves like the reference.
    """

    fact: Fact = Fact.DOFACT
    equil: bool = True
    col_perm: ColPerm = ColPerm.ND_AT_PLUS_A
    row_perm: RowPerm = RowPerm.LargeDiag_MC64
    replace_tiny_pivot: bool = True
    iter_refine: IterRefine = IterRefine.SLU_DOUBLE
    trans: Trans = Trans.NOTRANS
    # DiagInv (reference default YES-iff-LAPACK, SRC/util.c:397-401):
    # precompute inverted diagonal blocks so device solves replace
    # triangular solves with batched GEMMs — pays off for repeated /
    # many-RHS solves.  Env SLU_TPU_DIAG_INV=1 flips the default (the
    # hardware solve-ladder sweep knob).
    diag_inv: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_DIAG_INV"))
    # PStatPrint analog reachable without code: SLU_TPU_STATS=1 flips the
    # default so any driver run (CLI, examples, embedding callers) prints
    # the options banner + full Stats.report (incl. the solve-health
    # line) — see docs/OBSERVABILITY.md
    print_stat: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_STATS"))
    # --- symbolic / blocking tuning (sp_ienv analogs, SRC/sp_ienv.c:70-123) ---
    # NREL: amalgamate subtrees with <= relax cols
    relax: int = dataclasses.field(
        default_factory=lambda: _env_int("NREL", 20))
    # NSUP: cap supernode width.  The reference uses 128 (CPU-cache-sized);
    # the MXU wants wider panels (SURVEY.md §7 step 10).
    max_supernode: int = dataclasses.field(
        default_factory=lambda: _env_int("NSUP", 256))
    # --- TPU-native knobs -----------------------------------------------------
    factor_dtype: str | None = None   # None => float32 on TPU, float64 on CPU
    ir_dtype: str = "float64"         # residual precision for refinement
    # fill-tolerant supernode amalgamation (symbfact.amalgamate_supernodes):
    # merged-front flops may grow up to this factor per merge.  The MXU
    # wants wide pivots; the measured padding/dispatch win dwarfs the
    # ≤ tol structural-flop cost.  0 disables (reference-style zero-fill
    # supernodes + leaf relaxation only).
    amalg_tol: float = dataclasses.field(
        default_factory=lambda: _env_float("SLU_TPU_AMALG_TOL", 1.2))
    bucket_growth: float = 1.5        # geometric padding factor for front
                                      # size buckets (static-shape batching)
    min_bucket: int = dataclasses.field(   # smallest padded front dimension
        default_factory=lambda: _env_int("SLU_TPU_MIN_BUCKET", 8))
    # factor-group scheduler (numeric/plan.py): "dataflow" packs ready
    # supernodes into maximal same-shape batches across elimination
    # levels (dispatch-count collapse); "level" is the strict
    # level-lockstep partition kept selectable for A/B — the two produce
    # bitwise-identical L/U (tests/test_schedule.py)
    schedule: str = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_SCHEDULE"))
    # dataflow look-ahead window in elimination levels: bounds how far
    # past the oldest incomplete level ready work may be pulled forward,
    # so Schur-pool liveness stays bounded (1 = level order, 0 = unbounded)
    sched_window: int = dataclasses.field(
        default_factory=lambda: env_int("SLU_TPU_SCHED_WINDOW"))
    # shape-key coalescing tolerance: merged batches may execute up to
    # this factor of their members' original padded flops (<= 1
    # disables).  Applied before the schedule branch, so "level" and
    # "dataflow" pad identically and stay bitwise-comparable.
    sched_align: float = dataclasses.field(
        default_factory=lambda: env_float("SLU_TPU_SCHED_ALIGN"))
    # Schur-update GEMM precision tier (ops/dense.gemm_precision):
    # None resolves the SLU_TPU_GEMM_PREC knob (empty knob = "default",
    # the single-pass tensorfloat-analog fast path, with legacy
    # SLU_TPU_PRECISION interop).  Reduced tiers are made safe by the
    # gemm-precision escalation rung: delivered BERR above the gate
    # refactors the same skeleton at the next-higher tier
    # (drivers/gssvx._escalate, docs/PERFORMANCE.md)
    gemm_prec: str | None = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_GEMM_PREC") or None)
    # numeric executor selection (numeric/factor.get_executor): "mega"
    # is the bucketed data-driven executor whose compiled-program count
    # is bounded by the closed shape-key set (numeric/mega.py) — pair it
    # with SLU_TPU_BUCKET_CLOSED=1 for the O(1)-in-n compile guarantee.
    # "auto" keeps the backend default (fused on CPU, stream elsewhere).
    executor: str = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_EXECUTOR"))
    # close the shape-key set at plan build (numeric/plan._close_shape_keys)
    bucket_closed: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_BUCKET_CLOSED"))
    # device-solve sweep scheduler (solve/plan.py): "dataflow" regroups
    # supernodes across levels into maximal same-shape sweep batches
    # (the serving hot path); "level" and "factor" are the A/B tiers —
    # all three produce the same solution through the same factors
    # (tests/test_solve_plan.py)
    solve_schedule: str = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_SOLVE_SCHEDULE"))
    # solve-scheduler look-ahead window (0 = unbounded: no Schur pool
    # bounds the solve, unlike the factor's sched_window)
    solve_window: int = dataclasses.field(
        default_factory=lambda: env_int("SLU_TPU_SOLVE_WINDOW"))
    # solve-side shape-key coalescing tolerance on top of the factor
    # keys (<= 1 disables; promoted panels get identity/zero padding)
    solve_align: float = dataclasses.field(
        default_factory=lambda: env_float("SLU_TPU_SOLVE_ALIGN"))
    # shard the Schur update pool across ALL mesh devices (the n≈1M
    # memory path; only meaningful with a grid) — SLU_TPU_POOL_PARTITION=1
    pool_partition: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_POOL_PARTITION"))
    # distributed analysis for the multi-process tier (the reference's
    # options->ParSymbFact: ParMETIS ordering + psymbfact): ordering and
    # symbolic work/memory partition across the ranks instead of running
    # on root (parallel/panalysis.py) — SLU_TPU_PAR_SYMB_FACT=1
    par_symb_fact: bool = dataclasses.field(
        default_factory=lambda: env_flag("SLU_TPU_PAR_SYMB_FACT"))
    # user-supplied permutations for MY_PERMC / MY_PERMR (real dataclass
    # fields so Options(user_perm_c=...) works — the reference reads these
    # from ScalePermstruct->perm_c/perm_r when ColPerm/RowPerm say MY_*).
    # compare=False: ndarray values would make the generated __eq__ raise.
    user_perm_c: object = dataclasses.field(default=None, compare=False)
    user_perm_r: object = dataclasses.field(default=None, compare=False)
    # solver health & recovery: condition estimation, non-finite sentinels,
    # and the automatic escalation ladder (see RecoveryPolicy)
    recovery: RecoveryPolicy = dataclasses.field(
        default_factory=RecoveryPolicy)
    # --- crash consistency (persist/, docs/RELIABILITY.md) -----------------
    # cooperative factorization deadline: checked between dispatch
    # groups, checkpoint flushed first, DeadlineExceededError raised —
    # collectively (flag allreduce) on the multi-rank path so
    # cancellation can never strand a peer in a collective.  None = off.
    deadline_s: float | None = dataclasses.field(
        default_factory=lambda: env_float("SLU_TPU_DEADLINE_S") or None)
    # factor-checkpoint interval in completed dispatch groups (0 = off);
    # arming it forces the streamed executor (the fused whole-program
    # jit has no group boundaries to checkpoint at)
    ckpt_every: int = dataclasses.field(
        default_factory=lambda: env_int("SLU_TPU_CKPT_EVERY"))
    # checkpoint bundle directory ("" = .slu_ckpt in the working dir)
    ckpt_dir: str = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_CKPT_DIR"))
    # --- rank-failure tolerance (parallel/recover.py) ----------------------
    # what a declared-dead peer rank does to a fault-tolerant driver
    # (pgssvx_ft): "abort" re-raises RankFailureError, "shrink" resumes
    # on the survivors, "respawn" replaces the dead rank with a fresh
    # process.  Only consulted by the FT epoch loop — plain pgssvx
    # always surfaces the structured error to its caller.
    ft: str = dataclasses.field(
        default_factory=lambda: env_str("SLU_TPU_FT"))


def set_default_options() -> Options:
    """Analog of set_default_options_dist (SRC/util.c:376).  The sp_ienv
    environment tier applies to every Options() construction (see the
    class docstring), so this is a plain constructor alias."""
    return Options()


def print_options(o: Options) -> str:
    """print_options_dist analog (SRC/util.c:405-439)."""
    lines = ["**************************************************",
             ".. options:"]
    for f in dataclasses.fields(o):
        v = getattr(o, f.name)
        if f.name in ("user_perm_c", "user_perm_r"):
            # summarize, never dump an n-entry permutation into the banner
            v = None if v is None else f"<perm len={len(v)}>"
        elif f.name == "recovery":
            v = (f"enabled={v.enabled} sentinels={v.sentinels} "
                 f"condest={v.condest}")
        lines.append(f"**    {f.name:<20s} {getattr(v, 'name', v)}")
    lines.append("**************************************************")
    return "\n".join(lines)


def default_factor_dtype() -> str:
    """float32 on TPU (no fp64 MXU), float64 on the CPU with x64."""
    import jax
    if (jax.default_backend() == "cpu"
            and os.environ.get("JAX_ENABLE_X64", "").lower()
            not in ("0", "false")
            and jax.config.read("jax_enable_x64")):
        return "float64"
    return "float32"

#!/usr/bin/env bash
# ci_gates.sh — the ONE entry point for the repo's non-pytest CI gates.
#
# Consolidates (shared contract: each gate exits non-zero on ANY
# regression, produces its diagnostics on stdout/stderr, and runs under
# a hard per-gate timeout):
#
#   slulint         scripts/run_slulint.sh          static analysis
#                   (SLU101-SLU105 + SLU107-SLU110, interprocedural
#                   tier) over the package, scripts/, bench.py and
#                   examples/
#   nan-guards      scripts/check_nan_guards.sh     JAX_DEBUG_NANS smoke
#   trace-overhead  scripts/check_trace_overhead.py tracer off-path
#                   allocation + artifact well-formedness
#   verify-overhead scripts/check_verify_overhead.py  SLU106 lockstep
#                   verifier: disabled path allocates no verifier state,
#                   enabled path round-trips and counts checks; plus the
#                   SLU109 lock-order verifier (SLU_TPU_VERIFY_LOCKS):
#                   off path hands out plain locks and builds no watch,
#                   on path records the order graph
#   schedule-equiv  scripts/check_schedule_equiv.py   level vs dataflow
#                   dispatch schedules produce bitwise-identical L/U;
#                   dataflow never exceeds the level group count
#   perf-regress    scripts/check_perf_regress.py     micro-bench factor
#                   GFLOP/s vs the bench-history median (noise-tolerant,
#                   self-seeding on an empty history)
#   slo-gate        scripts/check_slo.py              serve-path p99
#                   latency per nrhs size (real SolveServer, always-on
#                   obs/slo accounter) vs the bench-history median —
#                   LOWER-is-better, noise-tolerant, self-seeding on an
#                   empty history
#   crash-resume    scripts/check_crash_resume.py     kill -9 a
#                   factorization mid-run, resume from the durable
#                   checkpoint frontier, assert bitwise-identical L/U
#                   vs an uninterrupted run
#   rank-failure    scripts/check_rank_failure.py     kill -9 a rank
#                   mid-factor: every survivor raises RankFailureError
#                   within 2x SLU_TPU_COMM_TIMEOUT_S (no watchdog
#                   exit-3), and ft=shrink resumes the checkpoint
#                   frontier with bitwise-identical L/U
#   solve-equiv     scripts/check_solve_equiv.py      device batched
#                   solve: fused vs streamed bitwise-identical, sweep
#                   schedules agree, device vs host solve within f64
#                   tightness, nrhs padding reported honestly
#   serve-robust    scripts/check_serve_robust.py     SolveServer
#                   reliability: a poisoned column in a 64-column
#                   backlog fails exactly its own ticket (survivors
#                   bitwise vs a clean run), and an overload storm
#                   against a bounded queue sheds with structured
#                   errors instead of hanging
#   compile-budget  scripts/compile_census.py --buckets  the closed
#                   bucket set stays O(1): the mega executor's
#                   compiled-program count must be CONSTANT across
#                   n = 4096/32768/110592 (the BENCH_r02 compile-wall
#                   gallery), every bucket program AOT-stageable
#   tsan-native     scripts/check_tsan_native.sh      -fsanitize=thread
#                   build of the native shared segment + a threaded
#                   heartbeat/bulletin/seqlock stress; SKIPs loudly
#                   (never silent-green) when the toolchain lacks TSan
#   program-audit   scripts/check_program_audit.py    slulint v4 IR
#                   rules over the REAL executors: every jitted program
#                   (fused/stream/mega factor + device solve sweeps)
#                   passes SLU111 donation, SLU112 baked-const and
#                   SLU114 collective-lockstep audits under
#                   SLU_TPU_VERIFY_PROGRAMS=1; donation coverage 100%,
#                   baked const bytes 0
#   precision-safety scripts/check_precision_safety.py  throughput
#                   ladder: the bf16 GEMM tier on an ill-conditioned
#                   gallery matrix passes the componentwise-BERR gate
#                   or escalates (never delivers a failing X, with and
#                   without iterative refinement)
#   fleet-failover  scripts/check_fleet_failover.py   serving fleet:
#                   3 process replicas serving a mixed ≥8-matrix
#                   stream, kill -9 of one replica mid-stream loses
#                   zero accepted tickets with every delivered X
#                   bitwise vs an undisturbed run; a rolling deploy
#                   completes under traffic with zero dropped tickets
#                   and a poisoned bundle rolls back (preflight +
#                   per-replica canary)
#   precision-lint  scripts/check_precision_lint.py   slulint v5
#                   precision-flow rules: the whole tree is clean under
#                   SLU115 (implicit downcast), SLU116 (accumulation
#                   dtype), SLU117 (EFT purity) and SLU118 (tolerance
#                   hygiene); under SLU_TPU_VERIFY_DTYPES=1 every
#                   program the real executors submit (gate gallery,
#                   all three factor executors + device solve sweeps,
#                   plus a bf16-GEMM-tier run proving the sanctioned
#                   narrowing) passes the runtime dtype audit with zero
#                   findings and 100% census coverage
#   refactor-consistency scripts/check_refactor.py    crash-consistent
#                   same-pattern refactorization: refactor(handle,
#                   new_values) bitwise vs a SamePattern_SameRowPerm
#                   refresh with zero symbolic/fresh-compile seconds
#                   (fused/stream/mega); kill -9 MID-REFACTOR leaves
#                   the persisted state serving bitwise; a rolling
#                   fleet.refactor under live traffic drops zero
#                   tickets and a poisoned refactor rolls back every
#                   swapped replica
#   sharding-audit  scripts/check_sharding_audit.py   slulint v6
#                   sharding/memory rules: the whole tree is clean under
#                   SLU119 (implicit replication), SLU120 (mesh/spec
#                   hygiene vs utils/meshreg.py), SLU121 (static peak
#                   memory) and SLU122 (dispatch-loop cross-mesh
#                   transfers); under SLU_TPU_VERIFY_SHARDING=1 plus a
#                   generous SLU_TPU_MEM_BUDGET_BYTES every program the
#                   real executors submit (gate gallery, all three
#                   factor executors + device solve sweeps) audits
#                   clean with 100% census coverage and the mega bucket
#                   estimates within 2x of XLA memory_analysis; a tiny
#                   budget proves MemoryBudgetError fires BEFORE any
#                   program runs, naming the bucket rung
#   spmd-equiv      scripts/check_spmd_equiv.py       shard_map SPMD
#                   tier on the 8-virtual-device mesh: ONE compiled
#                   factor program regardless of n, L/U and solve/
#                   transpose-solve bitwise vs the fused+stream
#                   lockstep executors and the lockstep DeviceSolver,
#                   the demoted TreeComm tier still bitwise vs the
#                   gssvx driver (the A/B reference chain), and every
#                   mesh program audits clean (0 sharding findings,
#                   100% donation coverage) under the runtime auditors
#
# Scan sharing: the slulint gate (and any other in-tree slulint
# invocation) reads/writes the content-hash scan cache
# (.slulint-cache.json, analysis/cache.py), so the tree is parsed and
# dataflow-analyzed ONCE per content state — repeat gate invocations on
# an unchanged tree are sub-second cache hits.
#
# Usage:  scripts/ci_gates.sh [gate ...]      (default: all gates)
#         CI_GATE_TIMEOUT_S=900 scripts/ci_gates.sh
#
# Every gate runs even after an earlier one fails (CI wants the full
# picture); the exit code is the number of failed gates.  Wired for CI
# directly after the tier-1 pytest command (ROADMAP.md):
#
#   python -m pytest tests/ -q -m 'not slow' && scripts/ci_gates.sh
set -uo pipefail
cd "$(dirname "$0")/.."

TIMEOUT="${CI_GATE_TIMEOUT_S:-600}"

declare -A GATES=(
  [slulint]="scripts/run_slulint.sh"
  [nan-guards]="scripts/check_nan_guards.sh"
  [trace-overhead]="python scripts/check_trace_overhead.py"
  [verify-overhead]="python scripts/check_verify_overhead.py"
  [schedule-equiv]="python scripts/check_schedule_equiv.py"
  [solve-equiv]="python scripts/check_solve_equiv.py"
  [serve-robust]="python scripts/check_serve_robust.py"
  [perf-regress]="python scripts/check_perf_regress.py"
  [slo-gate]="python scripts/check_slo.py"
  [crash-resume]="python scripts/check_crash_resume.py"
  [rank-failure]="python scripts/check_rank_failure.py"
  [compile-budget]="python scripts/compile_census.py --buckets 16 32 48 --stage"
  [tsan-native]="scripts/check_tsan_native.sh"
  [program-audit]="python scripts/check_program_audit.py"
  [fleet-failover]="python scripts/check_fleet_failover.py"
  [precision-safety]="python scripts/check_precision_safety.py"
  [precision-lint]="python scripts/check_precision_lint.py"
  [refactor-consistency]="python scripts/check_refactor.py"
  [sharding-audit]="python scripts/check_sharding_audit.py"
  [spmd-equiv]="python scripts/check_spmd_equiv.py"
)
ORDER=(slulint precision-lint sharding-audit program-audit verify-overhead
       schedule-equiv solve-equiv spmd-equiv precision-safety serve-robust
       fleet-failover refactor-consistency crash-resume rank-failure
       compile-budget tsan-native trace-overhead nan-guards
       perf-regress slo-gate)

requested=("$@")
if [ ${#requested[@]} -eq 0 ]; then
  requested=("${ORDER[@]}")
fi

failed=0
for gate in "${requested[@]}"; do
  cmd="${GATES[$gate]:-}"
  if [ -z "$cmd" ]; then
    echo "ci_gates: unknown gate '$gate' (known: ${ORDER[*]})" >&2
    failed=$((failed + 1))
    continue
  fi
  echo "=== ci_gates: $gate (timeout ${TIMEOUT}s) ==="
  if timeout -k 10 "$TIMEOUT" $cmd; then
    echo "=== ci_gates: $gate OK ==="
  else
    rc=$?
    echo "=== ci_gates: $gate FAILED (rc=$rc) ===" >&2
    failed=$((failed + 1))
  fi
done

if [ "$failed" -ne 0 ]; then
  echo "ci_gates: $failed gate(s) failed" >&2
fi
exit "$failed"

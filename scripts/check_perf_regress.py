#!/usr/bin/env python
"""Perf-regression CI gate: micro-bench vs the bench-history baseline.

Runs the bench at a small, CI-affordable size (``PERF_GATE_NX``,
default 8 → n=512, CPU backend, ~seconds warm) and compares its factor
GFLOP/s against the MEDIAN of prior same-configuration rows in the
bench-history DB (scripts/bench_history.py).  Noise-tolerant by design:

* SELF-SEEDING — with no (or too few, < ``PERF_GATE_MIN_SAMPLES``)
  comparable history rows the gate appends the fresh row and passes, so
  the first CI run on a new machine is green and every later run has a
  baseline;
* the failure threshold is ``value < (1 - PERF_GATE_TOL) * median``
  (default tol 0.5 — CI machines are noisy; a real regression from a
  bad change is far larger than scheduler jitter);
* a failing row is still appended, flagged ``gate_fail`` so it never
  poisons the baseline median;
* compile-time creep is reported (WARN) when ``compile_seconds``
  exceeds (1 + 2·tol)·median, but does not fail the gate — cold/warm
  cache state legitimately swings it.

Usage:  check_perf_regress.py [--row FILE] [--history PATH]
  --row      compare an existing bench JSON row instead of running the
             micro-bench (used by the tests; FILE may be '-')
  --history  override the DB path (default: SLU_TPU_BENCH_HISTORY or
             .cache/bench_history.jsonl)

Gate contract (scripts/ci_gates.sh): exit 0 = pass/seeded, exit 1 =
regression or no measurement, diagnostics on stdout/stderr, runs under
the shared per-gate timeout.
"""

import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from superlu_dist_tpu.utils.options import env_float, env_int  # noqa: E402
from bench_history import (                                    # noqa: E402
    append_row, history_path, load_history, row_key)

#: history rows consulted for the baseline (most recent first)
BASELINE_WINDOW = 8


def fail(msg: str) -> "None":
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def run_micro_bench(nx: int) -> dict:
    """One bench row at gate size, pinned to the CPU backend (the gate
    must not depend on accelerator availability) with a bounded budget."""
    env = dict(os.environ,
               BENCH_NX=str(nx), BENCH_REPS="2", BENCH_DEADLINE_S="240",
               JAX_PLATFORMS="cpu")
    # the gate measures the default configuration — a sweep knob left in
    # the CI environment would silently fork the history key
    env.pop("SLU_TPU_TRACE", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, cwd=REPO, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    if r.returncode != 0:
        sys.stderr.write(r.stderr.decode())
        fail(f"micro-bench failed (rc={r.returncode})")
    lines = [ln for ln in r.stdout.decode().strip().splitlines()
             if ln.strip()]
    if not lines:
        fail("micro-bench produced no JSON row")
    return json.loads(lines[-1])


def main(argv) -> int:
    row_file = None
    hist_path = None
    it = iter(argv)
    for a in it:
        if a == "--row":
            row_file = next(it, None)
        elif a == "--history":
            hist_path = next(it, None)
        else:
            print(__doc__, file=sys.stderr)
            return 2
    hist_path = hist_path or history_path()
    tol = env_float("PERF_GATE_TOL")
    min_samples = env_int("PERF_GATE_MIN_SAMPLES")

    if row_file:
        text = (sys.stdin.read() if row_file == "-"
                else open(row_file).read())
        lines = [ln for ln in text.strip().splitlines() if ln.strip()]
        row = json.loads(lines[-1])
    else:
        row = run_micro_bench(env_int("PERF_GATE_NX"))

    if row.get("value") is None:
        fail(f"bench row carries no measurement (phase="
             f"{row.get('phase')!r}, timeout={row.get('timeout')})")
    key = row_key(row)
    value = float(row["value"])

    prior = [h for h in load_history(hist_path)
             if h.get("history_key", row_key(h)) == key
             and h.get("value") is not None and not h.get("gate_fail")]
    if len(prior) < min_samples:
        append_row(row, hist_path)
        print(f"perf gate: SEEDED history ({len(prior)} -> "
              f"{len(prior) + 1} rows for [{key}]; enforcement starts at "
              f"{min_samples}) — value {value:.2f} GF/s")
        return 0

    window = prior[-BASELINE_WINDOW:]
    base = statistics.median(float(h["value"]) for h in window)
    floor = (1.0 - tol) * base
    ok = value >= floor
    append_row(row, hist_path, gate_fail=not ok)

    # compile-time creep: informational only (cache state swings it)
    comp = row.get("compile_seconds")
    comps = [float(h["compile_seconds"]) for h in window
             if h.get("compile_seconds")]
    if comp and comps:
        cbase = statistics.median(comps)
        if cbase > 0 and float(comp) > (1.0 + 2.0 * tol) * cbase:
            print(f"perf gate: WARN compile_seconds {comp:.2f}s vs "
                  f"median {cbase:.2f}s (cold cache?)")

    verdict = "OK" if ok else "REGRESSION"
    print(f"perf gate: {verdict} value {value:.2f} GF/s vs median "
          f"{base:.2f} over {len(window)} rows (floor {floor:.2f}, "
          f"tol {tol:.0%}) [{key}]")
    if not ok:
        print(f"FAIL: factor throughput regressed below the noise floor "
              f"— {value:.2f} < {floor:.2f} GF/s; inspect "
              f"'{sys.executable} scripts/bench_history.py list' and the "
              "compile census in the bench row", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

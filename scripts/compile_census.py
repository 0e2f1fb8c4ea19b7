#!/usr/bin/env python
"""Compile census report: which shape-key buckets dominate cold compile.

The diagnostic ROADMAP item 3 needs before anyone attempts the bucketed
mega-kernel: the n=110592 TPU factor died inside factor-compile
(BENCH_r02, 119 kernels / 455 groups) with no record of which buckets
ate the budget.  This script aggregates compile-census evidence from
any of the artifacts the telemetry layer now produces, or measures the
exact trace/lower/compile stage split live.

Usage:
  compile_census.py ARTIFACT [ARTIFACT ...]
      Aggregate ``compile`` records from any mix of:
        * obs trace artifacts (Chrome trace JSON or the JSONL sidecar,
          SLU_TPU_TRACE) — the ``compile``-category spans;
        * bench JSON rows — the ``compile_census`` field;
        * flight-recorder dumps — the embedded ``compile`` block.
  compile_census.py --live [NX]
      Build the bench plan for a poisson3d grid of edge NX (default 8)
      on the CPU backend and AOT-stage every distinct streamed-executor
      shape key, timing jaxpr trace, StableHLO lowering, and XLA
      compile SEPARATELY per bucket (the exact split the in-band census
      approximates with first-call wall time).  CPU compile cost ranks
      buckets the same way the TPU compiler does, ~proportionally.

  compile_census.py --buckets [NX ...] [--stage]
      The compile-BUDGET check (ci_gates.sh gate `compile-budget`):
      build the CLOSED bench plan (SLU_TPU_BUCKET_CLOSED semantics,
      numeric/plan._close_shape_keys) for a gallery of poisson3d sizes
      (default 16 32 48 — n = 4096 / 32768 / 110592, the BENCH_r02
      acceptance ladder) and FAIL (exit 1) unless the mega executor's
      compiled-program count is CONSTANT in n.  This is the invariant
      that killed BENCH_r02: the streamed kernel count grew with the
      matrix (119 kernels at n=110592) until compile time, not
      arithmetic, was the scaling wall.  --stage additionally
      AOT-stages (trace+lower, no backend compile) every bucket
      program, proving the closed set is buildable.

Output: per-bucket ranked table (seconds, share, builds, disk hits, and
— when the sharding twin audited the programs or --live staged them —
the SLU121 static peak-live-bytes estimate as a ``peak MiB`` column)
and the totals line.  Exit 1 when no census evidence is found.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# artifact parsing
# ---------------------------------------------------------------------------

def _iter_events(text: str):
    """Trace events from a Chrome trace JSON or JSONL sidecar, or None."""
    text = text.strip()
    if not text:
        return None
    if text.startswith("{"):
        try:
            doc = json.loads(text)
        except json.JSONDecodeError:
            doc = None
        if isinstance(doc, dict) and isinstance(doc.get("traceEvents"),
                                                list):
            return doc["traceEvents"]
        if isinstance(doc, dict):
            return None                # handled by the dict sniffers
    events = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            return None
        if not isinstance(ev, dict) or "cat" not in ev:
            return None
        events.append(ev)
    return events or None


def rows_from_artifact(path: str) -> list:
    """[{site, key, seconds, builds, persistent_hits}] from one file, []
    when the file carries no census evidence."""
    try:
        text = open(path).read()
    except OSError as e:
        print(f"compile_census: cannot read {path!r}: {e}",
              file=sys.stderr)
        return []
    # bench row / flight dump: a single JSON dict with a census block
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        census = doc.get("compile_census")
        if census is None and isinstance(doc.get("compile"), dict):
            census = doc["compile"].get("census")
        if isinstance(census, list):
            return [dict(site=r.get("site", "?"), key=r.get("key", "?"),
                         seconds=float(r.get("seconds", 0.0)),
                         builds=int(r.get("builds", r.get("n", 1))),
                         persistent_hits=int(r.get("persistent_hits", 0)),
                         peak_bytes_est=int(r.get("peak_bytes_est", 0)))
                    for r in census]
    # trace artifact: compile-category spans
    events = _iter_events(text)
    if events is None:
        return []
    rows = []
    for ev in events:
        if ev.get("cat") != "compile":
            continue
        args = ev.get("args") or {}
        rows.append(dict(
            site=str(ev.get("name", "?")).replace("compile ", "", 1),
            key=str(args.get("key", "?")),
            seconds=float(ev.get("dur", 0.0)) / 1e6,   # trace dur is us
            builds=int(args.get("builds", 1)),
            persistent_hits=1 if args.get("persistent_hit") else 0))
    return rows


# ---------------------------------------------------------------------------
# live AOT staging
# ---------------------------------------------------------------------------

def live_rows(nx: int) -> list:
    """AOT-stage every distinct streamed shape key of the bench plan and
    time trace / lower / compile separately (CPU backend; double work is
    fine offline — the in-band census never does this)."""
    import time

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")
    from jax import ShapeDtypeStruct as Sds
    import jax.numpy as jnp

    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric import stream
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    a = poisson3d(nx)
    sym = symmetrize_pattern(a)
    sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym),
                            relax=128, max_supernode=256, amalg_tol=1.05)
    plan = build_plan(sf, min_bucket=16, growth=1.05)
    ex = stream.StreamExecutor(plan, "float32")
    n_avals = len(plan.pattern_indices)
    print(f"live census: n={a.n_rows}, {len(plan.groups)} groups, "
          f"{ex.n_kernels} distinct shape keys")

    rows, seen = [], set()
    f32 = jnp.dtype("float32")
    i64 = jnp.dtype("int64")
    for key, _, child_arrs, _, _ in ex._steps:
        if key in seen:
            continue
        seen.add(key)
        (b, m, w, u), la, child_shapes, pool_size, dtype = key
        # the step signature of stream._kernel, as ShapeDtypeStructs
        args = [Sds((n_avals,), f32), Sds((pool_size,), f32),
                Sds((), f32),
                Sds((la,), i64), Sds((la,), i64), Sds((la,), i64),
                Sds((b,), i64), Sds((b,), i64)]
        for (ub, c) in child_shapes:
            args += [Sds((c,), i64), Sds((c,), i64), Sds((c, ub), i64)]
        kern = stream._kernel(key[0], la, child_shapes, pool_size, dtype,
                              None, False, "blocked")
        peak = _static_peak(kern, args, f"lu b{b} m{m} w{w} u{u}")
        t0 = time.perf_counter()
        traced = kern.trace(*args)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        lowered.compile()
        t3 = time.perf_counter()
        rows.append(dict(site="stream._kernel",
                         key=f"lu b{b} m{m} w{w} u{u}",
                         seconds=t3 - t0, builds=1, persistent_hits=0,
                         peak_bytes_est=peak,
                         trace_s=t1 - t0, lower_s=t2 - t1,
                         compile_s=t3 - t2))
    return rows


def _static_peak(kern, args, label: str) -> int:
    """SLU121 static high-water live bytes of one abstractly-traced
    kernel (analysis/program.py liveness walk) — the census memory
    column.  0 when the trace fails."""
    try:
        from superlu_dist_tpu.analysis.program import (audit_sharding,
                                                       trace_spec)
        spec = trace_spec(kern, tuple(args), label=label, site="census")
        _, stats = audit_sharding(spec, 1 << 20)
        return int(stats.get("peak_bytes_est", 0))
    except Exception as e:
        print(f"compile_census: static peak unavailable for {label}: {e}",
              file=sys.stderr)
        return 0


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def report(rows: list, staged: bool) -> int:
    if not rows:
        print("compile_census: no census evidence found (pass a trace "
              "artifact, bench row, or flight dump — or use --live)",
              file=sys.stderr)
        return 1
    agg: dict[tuple, dict] = {}
    for r in rows:
        row = agg.setdefault((r["site"], r["key"]), dict(
            site=r["site"], key=r["key"], seconds=0.0, builds=0,
            persistent_hits=0, peak_bytes_est=0,
            trace_s=0.0, lower_s=0.0, compile_s=0.0))
        row["seconds"] += r["seconds"]
        row["builds"] += r.get("builds", 1)
        row["persistent_hits"] += r.get("persistent_hits", 0)
        row["peak_bytes_est"] = max(row["peak_bytes_est"],
                                    r.get("peak_bytes_est", 0))
        for k in ("trace_s", "lower_s", "compile_s"):
            row[k] += r.get(k, 0.0)
    ranked = sorted(agg.values(), key=lambda row: -row["seconds"])
    total = sum(row["seconds"] for row in ranked) or 1e-12
    builds = sum(row["builds"] for row in ranked)
    hits = sum(row["persistent_hits"] for row in ranked)
    # memory column (slulint v6): the SLU121 static peak-live-bytes
    # estimate, present when the sharding twin audited the program or
    # --live staged it — the will-it-fit-HBM axis next to compile cost
    have_mem = any(row["peak_bytes_est"] for row in ranked)
    print(f"\n== compile census: {builds} builds, {total:.2f} s total, "
          f"{hits} persistent-cache hits ==")
    hdr = "   seconds  share  builds  hits"
    if have_mem:
        hdr += "  peak MiB"
    hdr += "  site                key"
    if staged:
        hdr += "                        trace/lower/compile"
    print(hdr)
    for row in ranked:
        line = (f"  {row['seconds']:8.3f}  {100 * row['seconds'] / total:4.1f}%"
                f"  {row['builds']:6d}  {row['persistent_hits']:4d}")
        if have_mem:
            line += f"  {row['peak_bytes_est'] / (1 << 20):8.2f}"
        line += f"  {row['site']:<18s}  {row['key']:<24s}"
        if staged:
            line += (f"  {row['trace_s']:.3f}/{row['lower_s']:.3f}"
                     f"/{row['compile_s']:.3f} s")
        print(line)
    top = ranked[0]
    print(f"\ndominant bucket: {top['key']} ({top['site']}) — "
          f"{100 * top['seconds'] / total:.1f}% of compile time")
    if have_mem:
        worst = max(ranked, key=lambda row: row["peak_bytes_est"])
        print(f"peak static memory: {worst['key']} ({worst['site']}) — "
              f"{worst['peak_bytes_est'] / (1 << 20):.2f} MiB estimated "
              f"live high-water (SLU121 model)")
    return 0


# ---------------------------------------------------------------------------
# closed-bucket budget check (the `compile-budget` CI gate)
# ---------------------------------------------------------------------------

def bucket_budget(nxs: list, stage: bool) -> int:
    """Closed bucket sets across a size gallery: print one line per
    size, fail unless the mega program count is constant in n."""
    import jax
    jax.config.update("jax_platforms", "cpu")

    from superlu_dist_tpu.models.gallery import poisson3d
    from superlu_dist_tpu.numeric.mega import MegaExecutor, _mega_kernel
    from superlu_dist_tpu.numeric.plan import build_plan
    from superlu_dist_tpu.ordering.dispatch import get_perm_c
    from superlu_dist_tpu.sparse.formats import symmetrize_pattern
    from superlu_dist_tpu.symbolic.symbfact import symbolic_factorize
    from superlu_dist_tpu.utils.options import Options

    import numpy as np
    import jax.numpy as jnp
    import time

    counts = {}
    for nx in nxs:
        t0 = time.perf_counter()
        a = poisson3d(nx)
        sym = symmetrize_pattern(a)
        sf = symbolic_factorize(sym, get_perm_c(Options(), a, sym),
                                relax=128, max_supernode=256,
                                amalg_tol=1.05)
        plan = build_plan(sf, min_bucket=16, growth=1.05, closed=True)
        ex = MegaExecutor(plan, "float32")
        staged, peak = 0, 0
        if stage:
            idt = jnp.asarray(np.zeros(0, dtype=np.int64)).dtype
            from jax import ShapeDtypeStruct as Sds
            f32 = jnp.dtype("float32")
            for key in sorted({k for k, _, _, _, _ in ex._steps},
                              key=str):
                (b, m, w, u), la, (ns_, cm, ub), pl, av, dt = key
                args = (Sds((av,), f32), Sds((pl,), f32), Sds((), f32),
                        Sds((la,), idt), Sds((la,), idt),
                        Sds((la,), idt), Sds((b,), idt), Sds((b,), idt),
                        Sds((ns_, cm), idt), Sds((ns_, cm), idt),
                        Sds((ns_,), idt), Sds((ns_, cm, ub), idt))
                kern = _mega_kernel(*key, "blocked")
                try:
                    kern.trace(*args).lower()
                except AttributeError:
                    kern.lower(*args)
                # static peak (SLU121) of the worst bucket program: the
                # budget gate's compile-count invariant says nothing
                # about whether the rung-padded pool still FITS — this
                # column does
                peak = max(peak, _static_peak(
                    kern, args, f"lu b{b} m{m} w{w} u{u} P{pl}"))
                staged += 1
        counts[nx] = ex.n_kernels
        mem = (f"peak={peak / (1 << 20):.2f}MiB " if peak else "")
        print(f"nx={nx:3d} n={a.n_rows:7d} groups={len(plan.groups):4d} "
              f"mega_kernels={ex.n_kernels} "
              f"digest={plan.bucket_set_digest()} "
              f"staged={staged} {mem}({time.perf_counter() - t0:.1f}s)",
              flush=True)
    distinct = sorted(set(counts.values()))
    if len(distinct) != 1:
        print(f"compile-budget: FAIL — compiled-program count is NOT "
              f"constant in n: {counts} (the closure pass must clamp "
              f"every gallery size to the same SLU_TPU_BUCKET_KEYS "
              f"bucket count)", file=sys.stderr)
        return 1
    print(f"compile-budget: OK — {distinct[0]} programs at every "
          f"gallery size (streamed-executor comparison: BENCH_r02 "
          f"needed 119 at n=110592)")
    return 0


def main(argv) -> int:
    if argv and argv[0] == "--buckets":
        rest = [a for a in argv[1:] if a != "--stage"]
        stage = "--stage" in argv[1:]
        nxs = [int(x) for x in rest] or [16, 32, 48]
        return bucket_budget(nxs, stage)
    if argv and argv[0] == "--live":
        nx = int(argv[1]) if len(argv) > 1 else 8
        return report(live_rows(nx), staged=True)
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    rows = []
    for path in argv:
        rows.extend(rows_from_artifact(path))
    return report(rows, staged=False)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python
"""Trace/metrics/flight-recorder overhead smoke run (check_nan_guards
style).

Runs a small factor+solve in fresh subprocesses:

* everything OFF — asserts the disabled paths allocate NO per-event
  telemetry objects: the process-global tracer stays the NULL_TRACER
  singleton (its spans feed only the profiler sink, with the no-op
  span as their sink), ``obs.metrics.get_metrics()`` stays
  the NULL_METRICS singleton (no counter dict entries), and
  ``obs.flightrec.get_flightrec()`` stays the NULL_FLIGHTREC singleton
  (no ring, no signal handler, no artifact file);
* tracing ON   — validates the artifacts: the Chrome trace JSON loads,
  carries phase + kernel + compile spans whose timestamps are monotone
  per thread, the kernel spans inside each FACT phase sum to its
  duration (within a slack factor), and the JSONL sidecar parses line
  by line;
* metrics + flight recorder ON — asserts the registry fills (scheduler
  gauges from the factorization) and a provoked dump leaves a
  well-formed postmortem (reason, anchor, events, compile census).

Exit 0 = pass.  One gate of scripts/ci_gates.sh (the consolidated CI
entry point); a few seconds on CPU.  Gate contract (shared with
run_slulint.sh, check_nan_guards.sh and check_verify_overhead.py): any
regression — a child failure, telemetry allocated on a disabled path,
a malformed artifact — raises/asserts, which exits non-zero.
"""

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the child: one small factor+solve through the expert driver, then a
# JSON line reporting what tracer the process ended up with
CHILD = r"""
import json, os, sys
import numpy as np
import superlu_dist_tpu as slu
from superlu_dist_tpu.models.gallery import poisson2d
from superlu_dist_tpu.obs import flightrec, metrics, trace
from superlu_dist_tpu.utils import tols

a = poisson2d(10)
b = np.ones(a.n_rows)
x, lu, stats, info = slu.gssvx(slu.Options(), a, b)
assert info == 0, info
res = float(np.linalg.norm(b - a.matvec(x)) / np.linalg.norm(b))
assert res < tols.RESID_GATE, res
t = trace.get_tracer()
m = metrics.get_metrics()
fr = flightrec.get_flightrec()
snap = m.snapshot()
out = {
    "tracer": type(t).__name__,
    "null_singleton": t is trace.NULL_TRACER,
    "span_sinkless": t.span("a")._sink is trace.NULL_SPAN,
    "fact_seconds": stats.utime["FACT"],
    "compile_builds": stats.compile.get("builds", 0),
    "metrics": type(m).__name__,
    "metrics_null": m is metrics.NULL_METRICS,
    "metrics_series": sum(len(v) for v in snap.values()) if snap else 0,
    "flightrec": type(fr).__name__,
    "flightrec_null": fr is flightrec.NULL_FLIGHTREC,
    "flightrec_ring": getattr(fr, "_ring", None) is not None,
}
if fr.enabled:
    out["dump"] = fr.dump("overhead-gate", detail="on-path check")
print(json.dumps(out))
"""


# serve-path child: two submits through a SolveServer with ALL obs
# knobs unset — both tickets must carry the shared NULL_TICKET
# singleton (zero TicketContext allocations per submit)
SERVE_CHILD = r"""
import json
import numpy as np
import superlu_dist_tpu as slu
from superlu_dist_tpu.models.gallery import poisson2d
from superlu_dist_tpu.obs import slo
from superlu_dist_tpu.serve.server import SolveServer

a = poisson2d(10)
_, lu, _, info = slu.gssvx(slu.Options(), a, np.ones(a.n_rows))
assert info == 0, info
with SolveServer(lu, max_wait_s=0.0) as srv:
    t1 = srv.submit(np.ones(a.n_rows))
    t2 = srv.submit(np.ones(a.n_rows))
    srv.flush()
    x1, x2 = t1.result(30.0), t2.result(30.0)
assert np.isfinite(x1).all() and np.isfinite(x2).all()
print(json.dumps({
    "ctx_null": t1._req.ctx is t2._req.ctx is slo.NULL_TICKET,
    "ctx_type": type(t1._req.ctx).__name__,
}))
"""


def run_child(extra_env, src=CHILD):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    for k in ("SLU_TPU_TRACE", "SLU_TPU_METRICS", "SLU_TPU_FLIGHTREC"):
        env.pop(k, None)
    env.update(extra_env)
    r = subprocess.run([sys.executable, "-c", src], env=env, cwd=REPO,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    if r.returncode != 0:
        sys.stderr.write(r.stderr.decode())
        raise SystemExit(f"child failed (rc={r.returncode})")
    return json.loads(r.stdout.decode().strip().splitlines()[-1])


def fail(msg):
    print(f"FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def main():
    tmp = tempfile.mkdtemp(prefix="slu_trace_check_")
    trace_path = os.path.join(tmp, "t.json")
    jsonl_path = os.path.join(tmp, "t.jsonl")

    # ---- off path: no telemetry objects, no artifacts --------------------
    off = run_child({})
    if off["tracer"] != "NullTracer" or not off["null_singleton"]:
        fail(f"disabled path allocated a tracer: {off}")
    if not off["span_sinkless"]:
        fail("disabled path gave a span a sink beyond the profiler")
    if os.path.exists(trace_path) or os.path.exists(jsonl_path):
        fail("disabled path created a trace artifact")
    if off["metrics"] != "NullMetrics" or not off["metrics_null"]:
        fail(f"disabled path allocated a metrics registry: {off}")
    if off["metrics_series"] != 0:
        fail(f"disabled path accumulated metric series: {off}")
    if off["flightrec"] != "NullFlightRecorder" or not off["flightrec_null"]:
        fail(f"disabled path allocated a flight recorder: {off}")
    if off["flightrec_ring"]:
        fail("disabled path allocated a flight-recorder ring")
    print(f"off: null tracer/metrics/flightrec, no artifact, "
          f"FACT {off['fact_seconds']:.3f}s")

    # ---- off path, serve tier: submits must not allocate a ticket
    # context — both tickets carry the one NULL_TICKET singleton
    serve_off = run_child({}, src=SERVE_CHILD)
    if not serve_off["ctx_null"]:
        fail(f"disabled serve path allocated a TicketContext: {serve_off}")
    print("off (serve): submits carry the shared NULL_TICKET singleton")

    # ---- on path: artifact exists and is well-formed ---------------------
    on = run_child({"SLU_TPU_TRACE": trace_path})
    if on["tracer"] != "Tracer":
        fail(f"SLU_TPU_TRACE did not install a Tracer: {on}")
    if not os.path.exists(trace_path):
        fail(f"no Chrome trace artifact at {trace_path}")
    if not os.path.exists(jsonl_path):
        fail(f"no JSONL sidecar at {jsonl_path}")

    doc = json.load(open(trace_path))
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        fail("traceEvents missing or empty")
    for ev in events:
        for k in ("name", "cat", "ph", "ts", "dur", "pid", "tid"):
            if k not in ev:
                fail(f"event missing field {k!r}: {ev}")
    cats = {ev["cat"] for ev in events}
    if not {"phase", "kernel"} <= cats:
        fail(f"expected phase+kernel spans, got categories {sorted(cats)}")
    # monotone start times per thread (the artifact is sorted)
    last = {}
    for ev in events:
        key = (ev["pid"], ev["tid"])
        if ev["ts"] < last.get(key, float("-inf")):
            fail(f"ts not monotone for {key}")
        last[key] = ev["ts"]
    # kernel spans within each FACT phase must account for its duration
    facts = [e for e in events if e["name"] == "FACT"
             and e["cat"] == "phase"]
    kernels = [e for e in events if e["cat"] == "kernel"]
    if not facts:
        fail("no FACT phase span")
    for f in facts:
        inner = sum(k["dur"] for k in kernels
                    if k["ts"] >= f["ts"]
                    and k["ts"] + k["dur"] <= f["ts"] + f["dur"] + 1)
        if not (0.25 * f["dur"] <= inner <= 1.05 * f["dur"]):
            fail(f"kernel spans ({inner:.0f}us) do not account for the "
                 f"FACT phase ({f['dur']:.0f}us)")
    n_rows = 0
    for line in open(jsonl_path):
        if line.strip():
            json.loads(line)
            n_rows += 1
    if n_rows != len(events):
        fail(f"JSONL rows ({n_rows}) != traceEvents ({len(events)})")
    # compile census: a fresh process builds its kernels, so the trace
    # must carry compile spans and the Stats block must count them
    if "compile" not in cats:
        fail(f"no compile-census spans in a cold run: {sorted(cats)}")
    if on["compile_builds"] < 1:
        fail(f"stats.compile recorded no builds: {on['compile_builds']}")
    anchors = [e for e in events if e["name"] == "clock-anchor"]
    if len(anchors) != 1 or "unix_time" not in anchors[0].get("args", {}):
        fail("missing/malformed wall-clock anchor event")
    print(f"on: {len(events)} spans, categories {sorted(cats)}, "
          f"artifact + sidecar well-formed, "
          f"{on['compile_builds']} censused builds")

    # ---- metrics + flight recorder on: registry fills, dump well-formed --
    fr_path = os.path.join(tmp, "fr.json")
    live = run_child({"SLU_TPU_METRICS": "1", "SLU_TPU_FLIGHTREC": fr_path})
    if live["metrics"] != "Metrics" or live["metrics_series"] < 1:
        fail(f"SLU_TPU_METRICS=1 did not fill the registry: {live}")
    if live["flightrec"] != "FlightRecorder" or live.get("dump") != fr_path:
        fail(f"SLU_TPU_FLIGHTREC did not install/dump: {live}")
    doc = json.load(open(fr_path))
    for key in ("reason", "anchor", "events", "compile", "phase_stack"):
        if key not in doc:
            fail(f"flight dump missing {key!r}: {sorted(doc)}")
    if not doc["events"]:
        fail("flight dump carries no events")
    print(f"metrics+flightrec on: {live['metrics_series']} series, "
          f"dump with {len(doc['events'])} events")

    # ---- repo hygiene: no stray postmortem dumps at the repo root --------
    # SLU_TPU_FLIGHTREC=1 (bare flag, no path) dumps flightrec-<pid>.json
    # into the cwd; a gate that provokes a dump without pointing it at a
    # tempdir litters the checkout (a flightrec-595.json once shipped in a
    # commit).  Every child above runs with an explicit artifact path, so
    # the repo root must stay clean.
    import glob
    stray = sorted(glob.glob(os.path.join(REPO, "flightrec-*.json")))
    if stray:
        fail(f"stray flight-recorder dump(s) at the repo root: {stray} "
             f"(point SLU_TPU_FLIGHTREC at a tempdir path)")
    print("hygiene: no stray flightrec-*.json at the repo root")
    print("trace overhead smoke: PASS")


if __name__ == "__main__":
    main()

"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one cell.  Set-up (from the first line of this file to the
window's start, as ``setup_s``): the matrix from the configuration, the
host analysis, the kernels (from the persistent compile cache after the
cell's first run in a checkout) and one full DOFACT ``gssvx``, which
compiles every program the window's calls run (the programs a call
re-traces come from the persistent cache).  The window then drives
``superlu_dist_tpu.gssvx`` in a closed loop with the mix's calls and ends
at the first call that returns at or after ``--seconds``.  After the
window the device's peak memory is read, the program's state is freed,
and the answers are checked against the plain reference
(``reference.py``).

The compile cache is ``.cache/jax`` inside the checkout, with no size cap,
whatever the environment says, so that only a cell's first run in a
checkout compiles.

With ``--trace 1`` the window runs under the JAX profiler and the line
carries the cell's per-layer metrics, ``busy_s``/``window_s`` and the
breakdown; with ``--trace 0`` its end-to-end metrics.  The last line of
standard output is the result; the numbers compared with the reference
come last in it, and again as the last lines of standard error.  Without
a TPU, or with fewer chips than the cell asks for, the run exits nonzero
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import matrices, reference, spec  # noqa: E402
from benchmark.traffic import Mix  # noqa: E402

#: the persistent compile cache: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
#: answers kept for the check, drawn from the seed (reservoir sampling)
CHECK_SAMPLE = 32
#: ladder rungs that refactor (drivers/gssvx._escalate)
REFACTOR_RUNGS = ("gemm-precision", "hiprec-factors", "refactor-rescale")
ANALYSIS_PHASES = ("EQUIL", "ROWPERM", "COLPERM", "ETREE", "SYMBFACT",
                   "DIST")


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


@dataclasses.dataclass
class Call:
    """One ``gssvx`` call of the window, as the program reported it."""

    latency_s: float
    info: int
    fact_s: float           # Stats.utime["FACT"], rung factorizations in
    rungs: list             # ladder rung names (stats.solve_report.rungs)
    factorizations: int     # the call's own (0 for FACTORED) + rungs'
    refine_steps: int


@dataclasses.dataclass
class Run:
    """What the metric readers (``metrics/<name>.py``) read."""

    cell: str
    setup_s: float
    window_s: float
    calls: list
    setup: dict             # Session.setup(): analysis seconds, compiles
    trace: dict | None      # trace_reduce.reduce() of the traced window


class CompileCounter:
    """JAX's own compile events: programs handed to the compiler, how many
    the persistent cache served, and backend compile seconds."""

    def __init__(self, jax):
        self.requests = self.hits = self.misses = 0
        self.backend_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.backend_s += secs

    def snapshot(self) -> dict:
        return {"requests": self.requests, "persistent_hits": self.hits,
                "persistent_writes": self.misses,
                "backend_compile_s": self.backend_s}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}


def control_options(options):
    """The control of ``correct``: the program's own single-precision path
    (float32 residuals in the refinement, no escalation ladder), one step
    below the float64 refinement the configurations state."""
    import superlu_dist_tpu as slu
    return dataclasses.replace(
        options, iter_refine=slu.IterRefine.SLU_SINGLE,
        recovery=dataclasses.replace(options.recovery, enabled=False))


class Session:
    """The program, the matrix and the handle of one process: set-up, then
    windows of calls.  ``gssvx`` may be replaced (the tests plant faults
    underneath the harness that way)."""

    def __init__(self, cell: spec.Cell, *, require_tpu=True, gssvx=None):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        # every program stays: a size cap set in the environment evicts
        # a cell's programs before its next run can read them
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")  # not in /tmp
        # the package sets the TPU compiler's flags, so it comes first
        import superlu_dist_tpu as slu
        import jax
        jax.config.update("jax_enable_x64", True)
        from superlu_dist_tpu.utils.jaxcache import enable_compile_cache
        enable_compile_cache()
        self.slu, self.jax, self.cell = slu, jax, cell
        self.devices = jax.devices()
        dev = self.devices[0]
        if require_tpu:
            if dev.platform != "tpu":
                raise SystemExit(f"no TPU: JAX's device is {dev.platform!r}")
            if len(self.devices) < cell.chips:
                raise SystemExit(f"{cell.name} needs {cell.chips} chips, "
                                 f"JAX finds {len(self.devices)}")
            spec.peaks(dev.device_kind)
            from superlu_dist_tpu import native
            native.require()
        self.gssvx = gssvx or slu.gssvx
        self.compiles = CompileCounter(jax)
        from superlu_dist_tpu.obs.compilestats import COMPILE_STATS
        self.census = COMPILE_STATS
        cfg = cell.config
        self.matrix = matrices.build(cfg["generator"], cfg["matrix"])
        log(f"{cell.name}: {cfg['generator']} n={self.matrix.n} "
            f"nnz={self.matrix.nnz} on {len(self.devices)} "
            f"{dev.platform} device(s) ({dev.device_kind})")
        self.lu = None
        self._a = (None, None)

    def operator(self, req):
        """The program's matrix for a request: the same object while the
        values stay the same, as a caller that reuses its matrix passes
        it (the program caches its device copy by identity)."""
        if self._a[0] != req.shift:
            a = self.slu.SparseCSR(self.matrix.n, self.matrix.n,
                                   self.matrix.indptr, self.matrix.indices,
                                   req.values)
            a.grid_shape = self.matrix.grid_shape
            self._a = (req.shift, a)
        return self._a[1]

    def call(self, options, req):
        """One gssvx call; returns (x, stats, Call)."""
        a = self.operator(req)
        with self.jax.profiler.TraceAnnotation("gssvx"):
            t0 = time.perf_counter()
            x, lu, stats, info = self.gssvx(options, a, req.b, lu=self.lu)
            lat = time.perf_counter() - t0
        if lu is not None:
            self.lu = lu
        names = ([r.name for r in stats.solve_report.rungs]
                 if stats.solve_report is not None else [])
        own = 0 if options.fact == self.slu.Fact.FACTORED else 1
        return x, stats, Call(
            latency_s=lat, info=int(info),
            fact_s=float(stats.utime.get("FACT", 0.0)), rungs=names,
            factorizations=own + sum(n in REFACTOR_RUNGS for n in names),
            refine_steps=int(stats.refine_steps))

    def setup(self, mix: Mix) -> dict:
        """DOFACT on request 0."""
        census0, jax0 = self.census.marker(), self.compiles.snapshot()
        req = mix.request(0)
        _, stats, first = self.call(self.slu.Options(
            **self.cell.config["options"]), req)
        if first.info != 0:
            raise RuntimeError(f"set-up DOFACT returned info={first.info}")
        analysis_s = sum(stats.utime[p] for p in ANALYSIS_PHASES)
        log(f"set-up DOFACT {first.latency_s:.3f}s: analysis "
            f"{analysis_s:.3f}s, factor {first.fact_s:.3f}s, rungs "
            f"{first.rungs}, {first.refine_steps} refinement steps")
        recs = self.census.census(since=census0)
        return {
            "analysis_s": analysis_s,
            "census_builds": int(sum(r["builds"] for r in recs)),
            "census_seconds": float(sum(r["seconds"] for r in recs)),
            "census_persistent_hits": int(sum(r["persistent_hits"]
                                              for r in recs)),
            **CompileCounter.delta(jax0, self.compiles.snapshot()),
        }

    def mix_options(self, mix: Mix):
        return self.slu.Options(**{**self.cell.config["options"],
                                   "fact": getattr(self.slu.Fact,
                                                   mix.fact)})

    def window(self, mix: Mix, seconds: float, options=None):
        """The mix's calls after the set-up's until one returns at or after
        ``seconds``.  Returns (window_s, calls, sample, failed),
        ``sample`` being up to CHECK_SAMPLE (request, x) pairs drawn from
        the seed, and ``failed`` the calls that raised or reported a
        nonzero info."""
        options = options or self.mix_options(mix)
        rng = np.random.default_rng([mix.seed, 2])
        calls, sample, failed = [], [], 0
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation("window"):
            while True:
                with self.jax.profiler.TraceAnnotation("rhs_gen"):
                    req = mix.request(1 + len(calls))
                try:
                    x, _, c = self.call(options, req)
                except Exception:  # a failed call is counted, not fatal
                    traceback.print_exc()
                    x, c = None, Call(0.0, -1, 0.0, [], 0, 0)
                calls.append(c)
                if c.info != 0 or x is None:
                    failed += 1
                else:
                    # reservoir: every answer has the same chance to be kept
                    j = len(calls) - 1
                    if j < CHECK_SAMPLE:
                        sample.append((req, np.array(x)))
                    else:
                        r = int(rng.integers(j + 1))
                        if r < CHECK_SAMPLE:
                            sample[r] = (req, np.array(x))
                if time.perf_counter() - t0 >= seconds:
                    break
        return time.perf_counter() - t0, calls, sample, failed

    def peak_bytes(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[:self.cell.chips]]
        return int(max(peaks))

    def release(self) -> None:
        """Free the program's state (handle, device arrays) before the
        reference runs."""
        self.lu = None
        self._a = (None, None)
        gc.collect()


def check(matrix, sample, limits: dict):
    """The largest of each compared number over the sampled answers, each
    beside its limit, and how many answers broke a limit."""
    worst = {k: 0.0 for k in reference.NUMBERS}
    bad = 0
    for req, x in sample:
        errs = reference.errors(matrix, req.values, x, req.x_true, req.b)
        bad += any(errs[k] > limits[k] for k in reference.NUMBERS)
        for k, v in errs.items():
            worst[k] = max(worst[k], v)
    return {k: {"value": worst[k], "limit": float(limits[k])}
            for k in reference.NUMBERS}, bad


def _reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, run: Run) -> dict:
    """Each metric's reader; a reader that finds nothing returns None and
    the metric is left out."""
    out = {}
    for m in entries:
        value = _reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True,
        cell: spec.Cell | None = None, gssvx=None) -> dict:
    """One run of a cell; returns the result line as a dict.  Tests pass
    ``cell`` (a smaller copy) and ``gssvx`` (a broken one) and skip the
    look for a chip."""
    cell = cell or spec.load_cell(workload)
    s = Session(cell, require_tpu=require_tpu, gssvx=gssvx)
    mix = Mix(cell.traffic, s.matrix, seed)
    setup = s.setup(mix)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = s.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans, no Python calls
        s.jax.profiler.start_trace(tdir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    jax0 = s.compiles.snapshot()
    window_s, calls, sample, failed = s.window(mix, seconds)
    window_compile = CompileCounter.delta(jax0, s.compiles.snapshot())
    if trace:
        s.jax.profiler.stop_trace()
    log(f"window {window_s:.3f}s, {len(calls)} calls, latencies "
        f"{[round(c.latency_s, 3) for c in calls][:16]}, factorizations "
        f"{[c.factorizations for c in calls][:16]}, refinement steps "
        f"{[c.refine_steps for c in calls][:16]}")
    print("set-up compiles: " + json.dumps(setup), flush=True)
    print("window compiles: " + json.dumps(window_compile), flush=True)
    dev = s.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(s.devices), "memory_peak_bytes": s.peak_bytes()}
    s.release()
    reduced = None
    if trace:
        from benchmark import trace_reduce
        try:
            reduced = trace_reduce.reduce_dir(tdir, n_devices=cell.chips)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    checks, bad = check(s.matrix, sample, cell.config["check"])
    r = Run(cell=cell.name, setup_s=setup_s, window_s=window_s, calls=calls,
            setup=setup, trace=reduced)
    correct = failed == 0 and bad == 0 and bool(sample)
    out = {"correct": correct, "attempted": len(calls),
           "failed": failed + bad,
           "metrics": read_metrics(cell.per_layer if trace
                                   else cell.end_to_end, r),
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']:.6e} (limit {v['limit']:.6e})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (its set-up is paid once).

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--seconds S]

For each of ``--seeds`` the timed path runs a window of ``--seconds``
(default: BENCHMARK.json's run_seconds) with that seed's traffic, exactly
as a benchmark run does, and the compared numbers are read over its
answers.  Then each of ``--control-seeds`` does the same with the control:
the program's own single-precision path (``run.control_options``), which
has to come out as not correct.  Every reading is one JSON line on
standard output; the last line sums them up: the largest reading of the
program (the lower reading of a limit) and the smallest of the control
(its upper reading).  The benchmark's own runs do not run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import reference, run, spec  # noqa: E402
from benchmark.traffic import Mix  # noqa: E402


def readings(cell, seeds, control_seeds, seconds, *, require_tpu=True,
             gssvx=None):
    """Yield one dict per seed: mode, seed, calls, failed and the compared
    numbers (largest over the window's sampled answers)."""
    s = run.Session(cell, require_tpu=require_tpu, gssvx=gssvx)
    s.setup(Mix(cell.traffic, s.matrix, seeds[0]))
    for mode, group in (("program", seeds), ("control", control_seeds)):
        for seed in group:
            mix = Mix(cell.traffic, s.matrix, seed)
            opts = s.mix_options(mix)
            if mode == "control":
                opts = run.control_options(opts)
            _, calls, sample, failed = s.window(mix, seconds, opts)
            checks, bad = run.check(s.matrix, sample, cell.config["check"])
            yield {"mode": mode, "seed": seed, "calls": len(calls),
                   "failed": failed, "over_limit": bad,
                   **{k: v["value"] for k, v in checks.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]

    def ints(text):
        return [int(x) for x in text.split(",") if x]

    rows = []
    for row in readings(cell, ints(args.seeds), ints(args.control_seeds),
                        seconds):
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for k in reference.NUMBERS:
        prog = [r[k] for r in rows if r["mode"] == "program"]
        ctrl = [r[k] for r in rows if r["mode"] == "control"]
        summary[k] = {"program_max": max(prog) if prog else None,
                      "control_min": min(ctrl) if ctrl else None,
                      "limit": cell.config["check"][k]}
    print(json.dumps({"workload": cell.name, "summary": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

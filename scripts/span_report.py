#!/usr/bin/env python
"""Where a profiled window's time went, by the program's own spans.

Reads a JAX profiler trace (an ``.xplane.pb``, or a directory holding
one) of a run whose host events include the program's
``slu.<cat>.<name>`` spans (``superlu_dist_tpu/obs/trace.py``) and prints
one JSON object:

- ``window_s``: the window, the host event named ``--window`` (the
  benchmark harness's ``window``), else every host event of the trace.
- ``spans``: per span name in the window, ``count``; ``seconds``, the
  durations clipped to the window and summed; ``self_seconds``, the same
  less the ``slu.*`` spans nested in them on their host line;
  ``idle_seconds``, the first chip's idle time inside the union of the
  name's intervals.
- ``idle_s`` and ``idle_by_span``: the first chip's idle time in the
  window, split by the innermost (shortest) ``slu.*`` span covering it,
  ``"(none)"`` outside every span.
- ``gaps``: each idle gap of at least ``--gap`` seconds, with the
  innermost span below the phase level (not ``slu.phase.*``) that covers
  at least 90 % of it, or null.
- ``events``: events per plane, what a trace's size follows.

The first chip is the ``XLA Ops`` line of the first ``/device:`` plane
that has one; a trace without such a plane reports no idle time (null).

Usage::

    python scripts/span_report.py TRACE [--window NAME] [--gap SECONDS]
"""

import argparse
import glob
import json
import os
import sys

PREFIX = "slu."
PHASE = "slu.phase."
OPS_LINE = "XLA Ops"
COVER = 0.9     # share of a gap a span must cover to name it


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle_intervals(ops, lo, hi) -> list:
    """The gaps between the union of ``ops`` intervals, inside [lo, hi)."""
    edges = [lo]
    for s, e in union((max(s, lo), min(e, hi)) for s, e in ops
                      if e > lo and s < hi):
        edges += [s, e]
    edges.append(hi)
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def overlap(a, b) -> int:
    """Total length of the intersection of two sorted disjoint interval
    lists."""
    total, j = 0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            total += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return total


def span_rows(lines, lo, hi, idle) -> dict:
    """``spans`` of the report: ``lines`` holds each host line's ``slu.*``
    events ``(name, start, end)``, ``idle`` the first chip's idle
    intervals (sorted, disjoint; None without a device), all in ns."""
    rows, ivs = {}, {}
    for events in lines:
        stack = []      # enclosing spans on this line: (end, row)
        for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
            while stack and stack[-1][0] <= s:
                stack.pop()
            d = min(e, hi) - max(s, lo)
            row = None
            if d > 0:
                row = rows.setdefault(name, {"count": 0, "seconds": 0,
                                             "self_seconds": 0})
                row["count"] += 1
                row["seconds"] += d
                row["self_seconds"] += d
                ivs.setdefault(name, []).append((max(s, lo), min(e, hi)))
                if stack and stack[-1][1] is not None:
                    stack[-1][1]["self_seconds"] -= d
            stack.append((e, row))
    return {name: {"count": row["count"],
                   "seconds": row["seconds"] * 1e-9,
                   "self_seconds": row["self_seconds"] * 1e-9,
                   "idle_seconds": (None if idle is None else
                                    overlap(union(ivs[name]), idle) * 1e-9)}
            for name, row in sorted(rows.items())}


def idle_by_span(spans, idle) -> dict:
    """Idle seconds by the shortest ``(name, start, end)`` span covering
    each stretch of ``idle``; a sweep over every boundary."""
    marks = []
    for i, (_, s, e) in enumerate(spans):
        marks += [(s, 1, i), (e, -1, i)]
    for s, e in idle:
        marks += [(s, 2, None), (e, -2, None)]
    marks.sort(key=lambda m: m[0])
    out, active, in_idle, prev = {}, set(), 0, None
    for t, kind, i in marks:
        if in_idle and prev is not None and t > prev:
            inner = min(active, default=None,
                        key=lambda j: spans[j][2] - spans[j][1])
            name = "(none)" if inner is None else spans[inner][0]
            out[name] = out.get(name, 0.0) + (t - prev) * 1e-9
        prev = t
        if kind == 1:
            active.add(i)
        elif kind == -1:
            active.discard(i)
        else:
            in_idle += kind // 2
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def gap_rows(spans, idle, lo, min_ns) -> list:
    """Each idle gap of at least ``min_ns`` with the shortest span below
    the phase level covering ``COVER`` of it."""
    out = []
    for s, e in idle:
        if e - s < min_ns:
            continue
        cover = [(ce - cs, name) for name, cs, ce in spans
                 if not name.startswith(PHASE)
                 and min(ce, e) - max(cs, s) >= COVER * (e - s)]
        out.append({"start_s": (s - lo) * 1e-9, "seconds": (e - s) * 1e-9,
                    "span": min(cover)[1] if cover else None})
    return out


def report(planes, window="window", gap_s=1.0) -> dict:
    """The report of ``planes``: ``(plane name, [(line name, [(event
    name, start ns, end ns)])])``."""
    host, slu, ops, counts = [], [], None, {}
    for pname, lines in planes:
        counts[pname] = sum(len(evs) for _, evs in lines)
        if pname.startswith("/host:"):
            for _, evs in lines:
                host += evs
                slu.append([ev for ev in evs if ev[0].startswith(PREFIX)])
        elif pname.startswith("/device:") and ops is None:
            ops = next((evs for lname, evs in lines if lname == OPS_LINE),
                       None)
    marked = [(s, e) for name, s, e in host if name == window]
    if marked:
        lo, hi = marked[0]
    elif host:
        lo, hi = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        raise ValueError("no host events")
    idle = (None if ops is None else
            idle_intervals([(s, e) for _, s, e in ops], lo, hi))
    flat = [(n, max(s, lo), min(e, hi)) for evs in slu for n, s, e in evs
            if e > lo and s < hi]
    out = {"window_s": (hi - lo) * 1e-9,
           "spans": span_rows(slu, lo, hi, idle),
           "idle_s": None, "idle_by_span": None, "gaps": None,
           "events": counts}
    if idle is not None:
        out["idle_s"] = sum(e - s for s, e in idle) * 1e-9
        out["idle_by_span"] = idle_by_span(flat, idle)
        out["gaps"] = gap_rows(flat, idle, lo, gap_s * 1e9)
    return out


def load(path) -> list:
    """The planes of the trace at ``path`` (a directory: its newest
    ``.xplane.pb``), in the form :func:`report` takes."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = max(found, key=os.path.getmtime)
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return [(plane.name,
             [(line.name, [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]) for line in plane.lines])
            for plane in pd.planes]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help=".xplane.pb file or a directory")
    ap.add_argument("--window", default="window",
                    help="host event that bounds the window")
    ap.add_argument("--gap", type=float, default=1.0,
                    help="shortest idle gap listed, in seconds")
    args = ap.parse_args(argv)
    print(json.dumps(report(load(args.trace), args.window, args.gap)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

- The traced window is the host span named ``window`` (the harness's
  ``TraceAnnotation`` around its measured loop).
- A device's busy time is the union of the intervals of its op events
  (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), clipped to the
  window; ``busy_s`` is its mean over the chips used, and the idle share
  is 100·(1 − busy_s/window_s).
- ``device_ops``: ops by summed device time in the window (mean over the
  chips), the largest first, each named ``<program>:<op>``: the program
  is the enclosing event of the ``XLA Modules`` line (``jit_step`` for
  the factor kernels, for example), the op the HLO instruction's name.
- ``idle_gaps``: the longest gaps between busy intervals on the first chip,
  each named after what the host was doing: the shortest host event that
  covers at least half of the gap, else the host event that overlaps it
  most, else ``"(no host event)"``.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
TOP = 10


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _op_names(ops, modules):
    """Name each op ``<program>:<op>`` after the module event enclosing
    its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        op = name.split(" = ")[0].strip().lstrip("%")
        i = bisect.bisect_right(starts, s) - 1
        prog = (modules[i][0].split("(")[0] if i >= 0
                and modules[i][2] >= s else "?")
        out.append((f"{prog}:{op}", s, e))
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _label(gap, host):
    lo, hi = gap
    best, best_ov = None, 0.0
    covering = []
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov <= 0:
            continue
        if ov >= 0.5 * (hi - lo):
            covering.append((e - s, name))
        if ov > best_ov:
            best, best_ov = name, ov
    if covering:
        return min(covering)[1]
    return best or "(no host event)"


def reduce(path: str, n_devices: int = 1) -> dict:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: _events(line) for line in plane.lines}
            devices[int(m.group(1))] = _op_names(
                lines.get(OPS_LINE, []), lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    windows = [(s, e) for name, s, e in host if name == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    if len(devices) < n_devices:
        raise ValueError(f"{path}: {len(devices)} TPU planes, "
                         f"{n_devices} expected")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    used = sorted(devices)[:n_devices]
    busy, per_op = [], {}
    for d in used:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[d]
               if e > lo and s < hi]
        busy.append(sum(e - s for s, e in _union([(s, e)
                                                  for _, s, e in ops])))
        for n, s, e in ops:
            per_op[n] = per_op.get(n, 0) + (e - s)
    busy_s = sum(busy) / len(busy) * 1e-9
    first = _union([(s, e) for _, s, e in devices[used[0]]])
    first = _clip(first, lo, hi)
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    inner = [h for h in host if h[0] != WINDOW]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share_pct": 100.0 * (1.0 - busy_s / window_s),
        "device_ops": [[n, t * 1e-9 / len(used)] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(g, inner), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:TOP]],
    }


def reduce_dir(trace_dir: str, n_devices: int = 1) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(max(paths, key=os.path.getmtime), n_devices)

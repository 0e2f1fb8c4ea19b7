"""Profiler trace: 100 × (1 − busy/window), busy being the union of the
device's op intervals in the traced window, averaged over the chips."""


def read(run):
    return run.trace["idle_share_pct"] if run.trace else None

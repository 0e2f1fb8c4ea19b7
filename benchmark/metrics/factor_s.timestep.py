"""The program's FACT timer (Stats.utime["FACT"]) per window call, rung
factorizations included, as the mean over the window's steps."""


def read(run):
    steps = [c.fact_s for c in run.calls if c.info == 0]
    return sum(steps) / len(steps) if steps else None

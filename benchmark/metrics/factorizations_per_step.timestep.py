"""Factorizations per time step: the step's own plus one per refactoring
ladder rung (stats.solve_report.rungs), as the mean over the window."""


def read(run):
    calls = [c for c in run.calls if c.info == 0]
    return (sum(c.factorizations for c in calls) / len(calls)
            if calls else None)

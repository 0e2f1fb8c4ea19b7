"""Host clock from the harness's first line to the window's start: the
matrix, host analysis, kernel build or cache load, the set-up DOFACT and
one call of the mix."""


def read(run):
    return run.setup_s

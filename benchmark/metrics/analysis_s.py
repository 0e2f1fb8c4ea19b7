"""The program's phase timers (Stats.utime) EQUIL through DIST of the
set-up DOFACT: the host analysis (drivers/gssvx.analyze, native/)."""


def read(run):
    return run.setup["analysis_s"]

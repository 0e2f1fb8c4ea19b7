"""The program's compile census (obs/compilestats.COMPILE_STATS) over the
set-up: seconds of the factor kernel builds, a persistent-cache load
counting as a build."""


def read(run):
    return run.setup["census_seconds"]

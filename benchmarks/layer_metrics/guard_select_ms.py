"""Device time a step under `gt.guard`: the anomaly guard's (and the SDC
vote's) keep-old selects over every parameter and both Adam moments."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.GUARD)

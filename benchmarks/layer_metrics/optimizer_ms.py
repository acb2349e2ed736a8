"""Device time a step under `gt.optimizer`: Adam's update, applying it, and
the gradient norm."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.OPTIMIZER)

"""Device time a step under `gt.embed`, forward and backward: the embedding
gather and positions, and the scatter-add of its gradient."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.EMBED)

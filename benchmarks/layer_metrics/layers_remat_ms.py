"""Device time a step in recomputing the layer runs' forward inside their
backward (`--checkpoint 1`): ops under `gt.layers.r<k>` and
`checkpoint/rematted_computation`. Zero where nothing is recomputed."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.LAYERS_REMAT)

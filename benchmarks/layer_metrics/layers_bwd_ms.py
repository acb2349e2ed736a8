"""Device time a step in the backward pass of the layer runs: ops under
`transpose(jvp(gt.layers.r<k>))` that are not recomputation, the two flash
backward kernels among them."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.LAYERS_BWD)

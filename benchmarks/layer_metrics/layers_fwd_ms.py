"""Device time a step in the forward pass of the layer runs: ops under
`jvp(gt.layers.r<k>)` that are neither transposed nor recomputed, the flash
forward kernel among them. Device 0, from the trace."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.LAYERS_FWD)

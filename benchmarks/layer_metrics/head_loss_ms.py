"""Device time a step under `gt.head_loss`, forward and backward: the final
norm, the logits and the cross entropy."""

from benchmarks import scopes


def read(run):
    return scopes.ms_a_step(run, scopes.HEAD_LOSS)

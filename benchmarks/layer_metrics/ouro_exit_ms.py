"""Device time a step under `gt.exit` (models/base.looped_loss, models/parts/loop.py): the exit gate's logits
(a dot product a position a pass), the distribution over the passes, its entropy and the weighting of the
passes' cross entropies, forward and backward; the heads and the cross entropies themselves are
`head_loss_ms`. Device 0, from the trace. None where the traced program names no such scope."""

from benchmarks import scopes

EXIT = r"gt\.exit" + scopes.END


def read(run):
    return scopes.ms_a_step(run, EXIT) or None

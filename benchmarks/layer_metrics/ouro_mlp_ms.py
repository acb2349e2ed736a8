"""`mlp_ms` for the looped cell: device time a step under `gt.mlp`, the dense SwiGLU half of every layer
APPLICATION (`num_layers` x `loop_steps`: 45 % of the model's FLOPs), forward, recomputation and backward.
The same reader as `mlp_ms` under a name of its own: an accepted entry lists its own cells, and the
standing `mlp_roofline` beside it prices `num_layers` once."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

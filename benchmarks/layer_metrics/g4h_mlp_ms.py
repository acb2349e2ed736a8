"""`mlp_ms` for the Granite-4.0-H cell: device time a step under `gt.mlp`,
the dense SwiGLU half of all ten layers (64 % of the model's FLOPs), forward,
recomputation and backward. The same reader as `mlp_ms` under a name of its
own: an accepted entry lists its own cells and may only have cells appended
(PR 35's `q3n_` readers alike)."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

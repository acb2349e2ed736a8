"""`mlp_ms` for the Phi-4-mini-flash cell: device time a step under `gt.mlp`,
the dense SwiGLU half of all six layers (62 % of the model's FLOPs), forward,
recomputation and backward. The same reader as `mlp_ms` under a name of its
own: an accepted entry lists its own cells and may only have cells appended
(PR 39's `g4h_mlp_ms` alike)."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

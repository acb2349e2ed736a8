"""`mlp_ms` for the EvaByte cell: device time a step under `gt.mlp`, the dense
SwiGLU half of all four layers (63 % of the model's FLOPs and the largest part
of the step), forward, recomputation and backward. The same reader as `mlp_ms`
under a name of its own: an accepted entry lists its own cells and takes only
cells of its own configurations (tests/benchmarks/test_layer_parts.py; PR 39's
`g4h_mlp_ms` and PR 57's `phi4_mlp_ms` alike)."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

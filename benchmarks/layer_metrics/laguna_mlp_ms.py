"""`mlp_ms` in the Laguna cell: device time a step under `gt.mlp`, the leading
layer's dense SwiGLU of 8192 (12.6 % of the model's FLOPs), forward,
recomputation and backward. The same reader as `mlp_ms` under a name of its
own (`kimi_mlp_ms`, `g4h_mlp_ms` and `lfm2_mlp_ms` alike)."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

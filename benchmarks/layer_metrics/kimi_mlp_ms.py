"""`mlp_ms` in the Kimi-Linear cell: device time a step under `gt.mlp`, the
leading layer's dense SwiGLU of 9216 (17 % of the model's FLOPs), forward,
recomputation and backward. The same reader as `mlp_ms` under a name of its
own: an accepted entry lists its own cells and may only have cells appended
(`g4h_mlp_ms` alike)."""

from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.read(run)

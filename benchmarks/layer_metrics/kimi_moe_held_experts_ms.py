"""`moe_held_experts_ms` in the Kimi-Linear cell: device time a step under
`gt.moe.experts`, the grouped matmuls over the rows the 8 held experts were
sent (256 each at the even share) and SwiGLU, at K, N = 2304. The GLM cell's
reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_experts_ms


def read(run):
    return moe_held_experts_ms.read(run)

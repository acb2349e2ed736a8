"""`moe_held_experts_ms` in the LFM2-MoE cell: device time a step under
`gt.moe.experts`, the grouped matmuls over the rows the 8 held experts were
sent (2048 each at the even share, four of megablox's 512-row tiles) and
SwiGLU, at K, N = 2048, 2 x 1792. The GLM cell's reader, whose entry lists its
own cell."""

from benchmarks.layer_metrics import moe_held_experts_ms


def read(run):
    return moe_held_experts_ms.read(run)

"""`moe_held_experts_ms` in the Laguna cell: device time a step under
`gt.moe.experts`, the grouped matmuls over the rows the 32 held experts were
sent (256 each at the even share, half of megablox's 512-row tile) and SwiGLU,
at K, N = 2048, 2 x 512, on PR 47's window of the sorted rows. The GLM cell's
reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_experts_ms


def read(run):
    return moe_held_experts_ms.read(run)

"""`moe_held_experts_ms` in the Qwen3-Next cell: device time a step under
`gt.moe.experts`, the grouped matmuls over the rows sent to the 32 experts
held of 512 (160 rows an expert at the even share, under megablox's 512-row
tile), the zeroing of the rows skipped, the held kernels' casts and SwiGLU.
The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.ms_or_none(run, moe_held_ms.EXPERTS)

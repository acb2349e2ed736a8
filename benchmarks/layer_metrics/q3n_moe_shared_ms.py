"""`moe_shared_ms` in the Qwen3-Next cell: device time a step under
`gt.moe.shared`, the shared expert of width 512 and its sigmoid gate. The GLM
cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_shared_ms


def read(run):
    return moe_shared_ms.read(run)

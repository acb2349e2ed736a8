"""`moe_shared_ms` in the Laguna cell: device time a step under
`gt.moe.shared`, the ungated shared expert of width 512 in the four routed
blocks. The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_shared_ms


def read(run):
    return moe_shared_ms.read(run)

"""`moe_held_dispatch_ms` in the Kimi-Linear cell: device time a step in what
surrounds the held experts' matmuls (`gt.moe.router`, `gt.moe.dispatch`,
`gt.moe.combine`) of the four routed blocks, over all 8192 x 8 assignments a
block. Hidden 2304 is no multiple of 2048, so the rows move by XLA's gathers
(`ops/moe.rows_form`). The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_dispatch_ms


def read(run):
    return moe_held_dispatch_ms.read(run)

"""`moe_held_dispatch_ms` in the Laguna cell: device time a step in what
surrounds the held experts' matmuls (`gt.moe.router`, `gt.moe.dispatch`,
`gt.moe.combine`) of the four routed blocks, over all 8192 x 8 assignments a
block under a softmax router of 256 renormalised over its pick. Hidden 2048
and 65536 assignments: three of a block's five moves of rows are the Pallas
row movers (`ops/moe.rows_form`; the compile event's `moe_row_kernel_blocks`).
The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_dispatch_ms


def read(run):
    return moe_held_dispatch_ms.read(run)

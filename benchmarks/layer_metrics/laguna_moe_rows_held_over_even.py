"""`moe_rows_held_over_even` in the Laguna cell: the rows a step sent through
the 32 held experts' grouped matmuls over the even share (8192 x 8 x 32 / 256 =
8192 a block), the `step` counter `expert_rows_held_over_even`. 1 is what the
model FLOPs count. The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_rows_held_over_even


def read(run):
    return moe_rows_held_over_even.read(run)

"""`moe_rows_held_over_even` in the LFM2-MoE cell: the rows a step sent through
the 8 held experts' grouped matmuls over the even share (16384 x 4 x 8 / 32 =
16384 a block), the `step` counter `expert_rows_held_over_even`. 1 is what the
model FLOPs count. The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_rows_held_over_even


def read(run):
    return moe_rows_held_over_even.read(run)

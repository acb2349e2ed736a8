"""`moe_held_gmm_roofline` in the Laguna cell: the least time the chip could
take for the step's grouped-matmul calls (`gmm.N` / `tgmm.N` under
`gt.moe.experts/gmm_in` and `/gmm_out`) AT THE ROWS THE PROGRAM'S COUNTER
REPORTS (`expert_rows_held`, spread over the four routed blocks; K, N = 2048,
2 x 512 and 512, 2048: benchmarks/model_flops/laguna.py `gmm_cost`) over the
time they took: 32 experts of width 512 at 256 rows each, the narrowest
experts of any cell. The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_gmm_roofline


def read(run):
    return moe_held_gmm_roofline.read(run)

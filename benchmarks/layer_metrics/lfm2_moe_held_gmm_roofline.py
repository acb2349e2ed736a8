"""`moe_held_gmm_roofline` in the LFM2-MoE cell: the least time the chip could
take for the step's grouped-matmul calls (`gmm.N` / `tgmm.N` under
`gt.moe.experts/gmm_in` and `/gmm_out`) AT THE ROWS THE PROGRAM'S COUNTER
REPORTS (`expert_rows_held`, spread over the four routed blocks; K, N = 2048,
2 x 1792 and 1792, 2048: benchmarks/model_flops/lfm2_moe.py `gmm_cost`) over
the time they took: the first reading of a SHARE of the experts above
megablox's 512-row tile (2048 rows an expert at the even share). The GLM
cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_gmm_roofline


def read(run):
    return moe_held_gmm_roofline.read(run)

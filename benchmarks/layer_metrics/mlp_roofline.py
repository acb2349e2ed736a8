"""The least time the chip could take for the dense MLPs' matmuls over the
time the step spent under `gt.mlp` (`mlp_ms`). The least: one forward and one
backward of the up (and gate) and down projections at the cell's tokens a
device, for every layer, over the chip's peak FLOP/s (benchmarks/flops.py's
convention: 2 FLOPs a multiply-add, backward = 2 x forward, recomputation NOT
counted; under tp a device multiplies `ffn / tp` columns of all its replica's
tokens, under pp its stage's `layers / pp` layers). A recomputed forward, the
activation's passes and a pipeline's padding ticks are in the time and not in
the count, so the share is a lower bound of the matmuls' own and
cannot pass 100 %. Compute bound at these shapes (the kernels are read once
for 8192 tokens). None where there is no trace or no such scope."""

from benchmarks import flops
from benchmarks.layer_metrics import flash_roofline, mlp_ms


def mlp_train_flops(fields, tokens, tp=1):
    """Forward + backward FLOPs of the MLP matmuls of all layers for
    `tokens` tokens on one of `tp` devices."""
    hidden, _, _, _, ffn = flops._sizes(fields)
    kernels = 3 if fields.get("activation") == "swiglu" else 2
    fwd = kernels * 2.0 * hidden * ffn / tp
    return fields["num_layers"] * tokens * fwd * (1.0 + flops.BWD_FWD_RATIO)


def read(run):
    took = mlp_ms.read(run)
    if not took:
        return None
    cell = run["cell"]
    lay = flash_roofline.layout(cell)
    # a device's tokens of a whole step (all its microbatches) through its
    # stage's share of the layers
    tokens = cell.traffic["global_batch"] // lay["dp"] * cell.traffic["seq_length"]
    least = (mlp_train_flops(cell.fields, tokens, lay["tp"]) / lay["pp"]
             / run["peak"]["bf16_flops_per_s"])
    return 100.0 * least * 1e3 / took

"""The least time the chip could take for a looped step's SwiGLU matmuls over the time the step spent under
`gt.mlp` (`ouro_mlp_ms`). The least: one forward and one backward of gate, up and down at the cell's tokens
a device for every layer APPLICATION (benchmarks/model_flops/<flops>.py `mlp_train_flops`: `num_layers` x
`loop_steps` of them, where the standing `mlp_roofline` counts `num_layers`), over the chip's peak FLOP/s. A
recomputed forward and the activation's passes are in the time and not in the count, so the share cannot
pass 100 %. None where there is no trace, no such scope or no `mlp_train_flops`."""

from benchmarks import cells
from benchmarks.layer_metrics import ouro_mlp_ms


def read(run):
    took = ouro_mlp_ms.read(run)
    if not took or "flops" not in run["cell"].config:
        return None
    cell = run["cell"]
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "mlp_train_flops"):
        return None
    least = costs.mlp_train_flops(cell.fields, cell.tokens_a_step / cell.chips) / run["peak"]["bf16_flops_per_s"]
    return 100.0 * least * 1e3 / took

"""The least time the chip could take for the step's gated short convolutions'
gate passes over the time they took (`conv_gate_ms`, everything under
`gt.attn.conv_gate`). The least: for each convolution layer one forward and one
backward pass of the pass's operations and bytes at the cell's tokens a chip
(benchmarks/model_flops/<flops>.py `conv_gate_cost`: forward [B | C | u] read
and `C v` written; backward those three read again, the cotangent read,
d[B | C | u] written and the taps' gradient), each the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s: memory bound. A recomputed forward and
what an implementation writes between its fusions are in the time and not in
the least, so the share cannot pass 100 %. None where there is no trace, no
such scope or no `conv_gate_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import conv_gate_ms


def read(run):
    cell = run["cell"]
    took = conv_gate_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "conv_gate_cost"):
        return None
    tokens = cell.tokens_a_step / cell.chips
    least = costs.conv_layers(cell.fields) * sum(
        flops.least_time_s(costs.conv_gate_cost(cell.fields, tokens, which), run["peak"])[0]
        for which in ("fwd", "bwd"))
    return 100.0 * least * 1e3 / took

"""The least time the chip could take for the step's per-channel delta-rule
cores over the time they took (`kda_rule_ms`, everything under
`gt.attn.kda_rule`). The least: for each KDA layer one forward and one backward
pass of the RECURRENCE's operations and bytes at the cell's tokens a chip
(benchmarks/model_flops/<flops>.py `kda_cost`: three (d_k, d_v) products a
head a token forward, twice that backward; q, k, v, o, the gate a head AND
channel in float32, beta and their gradients moved once), each the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s. A recomputed forward, the
sub-blocks' decays, the chunks' solves and the states kept a chunk are in the
time and not in the least, so the share cannot pass 100 %. None where there is
no trace, no such scope or no `kda_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import kda_rule_ms


def read(run):
    cell = run["cell"]
    took = kda_rule_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "kda_cost"):
        return None
    tokens = cell.tokens_a_step / cell.chips
    least = costs.kda_layers(cell.fields) * sum(
        flops.least_time_s(costs.kda_cost(cell.fields, tokens, which), run["peak"])[0]
        for which in ("fwd", "bwd"))
    return 100.0 * least * 1e3 / took

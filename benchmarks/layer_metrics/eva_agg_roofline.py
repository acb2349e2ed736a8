"""The least time the chip could take for the step's EVA aggregations over the
time they took (`eva_agg_ms`, everything under `gt.attn.eva_agg`). The least:
for each layer one forward and one backward pass of the aggregation's
operations and bytes at the cell's tokens a chip and sequence length
(benchmarks/model_flops/<flops>.py `eva_cost`: the two products of a forward
and the five of a backward over the EXACT (query, key) pairs, a query's own
window up to itself and the pooled keys of earlier windows; q, k, v, the pooled
keys and values, the output and their cotangents moved once), each the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s. A recomputed forward,
the diagonal's whole blocks and the rows' statistics are in the time and not in
the least, so the share cannot pass 100 %. None where there is no trace, no
such scope or no `eva_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import eva_agg_ms


def read(run):
    cell = run["cell"]
    took = eva_agg_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "eva_cost"):
        return None
    tokens, seq = cell.tokens_a_step / cell.chips, cell.traffic["seq_length"]
    least = cell.fields["num_layers"] * sum(
        flops.least_time_s(costs.eva_cost(cell.fields, tokens, which, seq), run["peak"])[0]
        for which in ("fwd", "bwd"))
    return 100.0 * least * 1e3 / took

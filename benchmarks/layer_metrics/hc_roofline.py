"""The least time the chip could take for ALL of a step's hyper-connections over the time they took (`hc_ms`,
everything under `gt.hc`): numerator and denominator over the same work. The least, whatever implements them and
however it is fused (benchmarks/model_flops/<flops>.py `hc_cost`, a layer: both halves' coefficients, Sinkhorn
steps and mixes): a forward for each layer, a recomputed one where the trace shows one (`--checkpoint 1`) and a
backward, each the larger of FLOPs over peak FLOP/s and its least bytes over peak bytes/s, given only that the
n-stream array does not stay on the chip across a half's body: memory bound. Over `gt.hc` whole and not over
`gt.hc.mix`: XLA builds fusions across the nested scopes (a backward's dX is summed in the fusion of the
coefficients' matmul; the mean square reads the array the read mixes) and books each to ONE scope by its
principal op, so a share of one nested scope divides work by a time that holds part of it. Second and third reads
of X and dX' inside a pass, float32 copies of the streams, the widening and the final sum are in the time and not
in the least. None where there is no trace, no such scope or no `hc_cost`."""

from benchmarks import cells, flops, scopes
from benchmarks.layer_metrics import hc_ms
from benchmarks.layer_metrics.mlp_ms import both


def read(run):
    cell = run["cell"]
    took = hc_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "hc_cost"):
        return None
    tokens = cell.tokens_a_step / cell.chips
    passes = ["fwd", "bwd"] + (["remat"] if scopes.ms_a_step(run, both(hc_ms.nested(), scopes.REMAT)) else [])
    least = cell.fields["num_layers"] * sum(
        flops.least_time_s(costs.hc_cost(cell.fields, tokens, which), run["peak"])[0] for which in passes)
    return 100.0 * least * 1e3 / took

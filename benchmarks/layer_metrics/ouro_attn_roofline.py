"""The least time the chip could take for a looped step's causal attention over the time its kernels took
(`ouro_attn_ms`). The least, by the MODEL's work whatever implements it: for each of the `num_layers` x
`loop_steps` layer APPLICATIONS (benchmarks/model_flops/<flops>.py `applications`, `attn_cost`) the two
products of each forward the trace shows (forward, recomputed forward) and the five of a backward over the
causal half, q, k, v, o and their cotangents moved once; the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s. Whole blocks on the diagonal and the softmax statistics' traffic are in the time and not in
the least, so the share cannot pass 100 %. None where there is nothing to read or no `attn_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import ouro_attn_ms


def read(run):
    took, found = ouro_attn_ms.read(run), ouro_attn_ms.phases(run)
    if not took or "flops" not in run["cell"].config:
        return None
    cell = run["cell"]
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "attn_cost"):
        return None
    rows, seq = cell.traffic["global_batch"] // cell.chips, cell.traffic["seq_length"]
    forwards = sum(1 for phase in ("fwd", "remat") if found[phase][1])
    least = costs.applications(cell.fields) * sum(
        times * flops.least_time_s(costs.attn_cost(cell.fields, rows, seq, which), run["peak"])[0]
        for which, times in (("fwd", forwards), ("bwd", 1 if found["bwd"][1] else 0)))
    return 100.0 * least * 1e3 / took

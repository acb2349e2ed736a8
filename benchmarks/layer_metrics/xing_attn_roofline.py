"""The least time the chip could take for the causal attention of a model whose heads are NARROWER than the
one call that runs them, over the time jax's flash kernels took (`flash_ms`'s first family, by the kernels'
names). Both widths come from the configuration's own FLOPs module (benchmarks/model_flops/<flops>.py
`attn_cost`, a layer, and `attn_layers`, how many layers run the call): the MODEL needs a forward's two
products (q k^T at its q/k dims, p v at its v dims) for each forward the trace shows (the first, the recomputed
one) and a backward's five (three at q/k, two at v) over the causal half, where the kernels run 2 + 4 + 3
products at the call's padded width; the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s. The
padding's products and jax's second pass over the scores are in the time and not in the least, so the share
cannot pass 100 % (Xing4.0's 192 / 128 head in a call at 256: kernels at their own peak would read 52 %). The
reader names no cell and no family: the entry's `workloads` says where it reads (the standing `flash_roofline`
prices a call at `head_dim` and lists the cells it was accepted with). None where there is no trace, no such
kernel, or no `attn_cost` and `attn_layers`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import flash_ms


def read(run):
    cell = run["cell"]
    if not run.get("trace") or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    found = flash_ms.per_kernel(run)
    took = sum(s for s, _ in found.values())
    if not took > 0 or not hasattr(costs, "attn_cost") or not hasattr(costs, "attn_layers") or not found["dkv"][1]:
        return None
    rows, seq = cell.traffic["global_batch"] // cell.chips, cell.traffic["seq_length"]
    forwards = found["fwd"][1] / found["dkv"][1]  # forwards a backward: 2 under full recomputation
    least = costs.attn_layers(cell.fields) * sum(
        times * flops.least_time_s(costs.attn_cost(cell.fields, rows, seq, which), run["peak"])[0]
        for which, times in (("fwd", forwards), ("bwd", 1)))
    return 100.0 * least / took

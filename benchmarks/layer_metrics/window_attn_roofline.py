"""The least time the chip could take for the step's window-attention kernel
calls over the time they took (`window_attn_ms`). For each kernel: its FLOPs
and bytes at the cell's shapes (benchmarks/model_flops/<flops>.py
`window_kernel_cost`: the EXACT band's products, query i on min(i + 1, window)
keys, and each operand moved once, k and v at the key heads), the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, times the calls the trace
counts (so the second forward of full recomputation is counted as run, as
`flash_roofline` counts it). A kernel runs whole blocks along the band's two
edges, which are in the time and not in the count, so the share cannot pass
100 %. None where there is no trace, no such kernel or no
`window_kernel_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics.window_attn_ms import per_kernel


def read(run):
    cell = run["cell"]
    if not run.get("trace") or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "window_kernel_cost"):
        return None
    found = per_kernel(run)
    took = sum(s for s, _ in found.values())
    if not took > 0:
        return None
    batch, seq = cell.traffic["global_batch"] // cell.chips, cell.traffic["seq_length"]
    # a layer's call a kind a pass; the trace counts every layer's and every pass's
    least = sum(calls * flops.least_time_s(costs.window_kernel_cost(cell.fields, kind, batch, seq), run["peak"])[0]
                for kind, (_, calls) in found.items())
    return 100.0 * least / took

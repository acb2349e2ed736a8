"""The least time the chip could take for the step's Mamba-2 scans over the
time they took (`ssd_ms`, everything under `gt.attn.ssd`). The least: for each
state-space layer one forward and one backward pass of the RECURRENCE's
operations and bytes at the cell's tokens a chip
(benchmarks/model_flops/<flops>.py `ssd_cost`: two (d_head, d_state) products
a head a token forward, twice that backward; x, B, C, dt, y and their
gradients moved once), each the larger of FLOPs over peak FLOP/s and bytes
over peak bytes/s. A recomputed forward, the chunks' masks and the states kept
a chunk are in the time and not in the least, so the share cannot pass 100 %.
None where there is no trace, no such scope or no `ssd_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import ssd_ms


def read(run):
    cell = run["cell"]
    took = ssd_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "ssd_cost"):
        return None
    tokens = cell.tokens_a_step / cell.chips
    least = costs.ssm_layers(cell.fields) * sum(
        flops.least_time_s(costs.ssd_cost(cell.fields, tokens, which), run["peak"])[0]
        for which in ("fwd", "bwd"))
    return 100.0 * least * 1e3 / took

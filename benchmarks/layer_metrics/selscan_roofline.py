"""The least time the chip could take for the step's selective scans over the
time they took (`selscan_ms`, everything under `gt.attn.selscan`). The least:
for each Mamba-1 layer one forward and one backward pass of the RECURRENCE's
operations and bytes at the cell's tokens a chip
(benchmarks/model_flops/<flops>.py `selscan_cost`: two multiply-adds a
(channel, state) a token forward, twice that backward; x, dt, B, C, m and their
gradients moved once), each the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s. A recomputed forward, the states carried through HBM and the
states kept a chunk are in the time and not in the least, so the share cannot
pass 100 %. None where there is no trace, no such scope or no `selscan_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics import selscan_ms


def read(run):
    cell = run["cell"]
    took = selscan_ms.read(run)
    if not took or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "selscan_cost"):
        return None
    tokens = cell.tokens_a_step / cell.chips
    least = costs.mamba_layers(cell.fields) * sum(
        flops.least_time_s(costs.selscan_cost(cell.fields, tokens, which), run["peak"])[0]
        for which in ("fwd", "bwd"))
    return 100.0 * least * 1e3 / took

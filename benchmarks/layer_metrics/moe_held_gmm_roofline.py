"""The least time the chip could take for the step's grouped-matmul calls
over the rows the HELD experts were sent, over the time they took. The calls
are the Pallas megablox kernels (ops/moe.grouped_matmul with `first_group`;
custom calls named `gmm.N`, and `tgmm.N` for the kernels' gradient), told
apart by the scope they run under: `gt.moe.experts/gmm_in` (hidden x 2 width)
and `.../gmm_out` (width x hidden). Each call's FLOPs and bytes come from its
shapes (benchmarks/model_flops/<flops>.py `gmm_cost`) AT THE ROWS THE
PROGRAM'S COUNTER REPORTS (`expert_rows_held` of the `step` event: all routed
blocks' rows a step, spread evenly over `routed_blocks`), not at the even
share: a step that sent more rows did more work. Its least time is the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, times the calls the
trace counts (so a recomputed forward counts as run). None where there is no
trace, no such kernel, no counter or no `gmm_cost`."""

from benchmarks import cells, flops
from benchmarks.layer_metrics.moe_gmm_roofline import per_kind
from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter


def read(run):
    cell = run["cell"]
    if not run.get("trace") or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    rows = step_counter(run, "expert_rows_held")
    if rows is None or not hasattr(costs, "routed_blocks"):
        return None
    found = per_kind(run)
    took = sum(s for s, _ in found.values())
    if not took > 0:
        return None
    rows_a_call = rows / cell.chips / costs.routed_blocks(cell.fields)
    least = sum(calls * flops.least_time_s(
        costs.gmm_cost(cell.fields, kind, rows_a_call), run["peak"])[0]
        for kind, (_, calls) in found.items())
    return 100.0 * least / took

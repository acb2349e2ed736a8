"""The least time the chip could take for the step's grouped-matmul calls
over the time they took. The calls are the Pallas megablox kernels
(ops/moe.grouped_matmul; custom calls named `gmm.N`, and `tgmm.N` for the
kernels' gradient), told apart by the scope they run under:
`gt.moe.experts/gmm_in` (hidden x 2 width) and `.../gmm_out` (width x hidden). Each call's FLOPs and bytes come from its shapes
(benchmarks/model_flops/<flops>.py `gmm_cost`: the rows actually sent, every
held expert's kernel once), its least time is the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, times the calls the trace counts (so a
recomputed forward counts as run)."""

from benchmarks import cells, flops
from benchmarks.trace import ops_matching

KERNEL = r"^t?gmm[.:]"
KINDS = {"in": r"gt\.moe\.experts[^/]*/gmm_in/", "out": r"gt\.moe\.experts[^/]*/gmm_out/"}


def per_kind(run):
    """{kind: (seconds a step, calls a step)}"""
    return {kind: ops_matching(run["trace"], "%s.*%s" % (KERNEL, scope))
            for kind, scope in KINDS.items()}


def tokens_a_device(cell):
    return cell.tokens_a_step // cell.chips  # no tp, cp or pp under routed experts


def read(run):
    cell = run["cell"]
    if not run.get("trace") or "flops" not in cell.config:
        return None
    costs = cells.load_module(cell.root, "benchmarks/model_flops/%s.py" % cell.config["flops"])
    if not hasattr(costs, "gmm_cost"):
        return None
    found = per_kind(run)
    took = sum(s for s, _ in found.values())
    if not took > 0:
        return None
    least = sum(calls * flops.least_time_s(
        costs.gmm_cost(cell.fields, kind, tokens_a_device(cell)), run["peak"])[0]
        for kind, (_, calls) in found.items())
    return 100.0 * least / took

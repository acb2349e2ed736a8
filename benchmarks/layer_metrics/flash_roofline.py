"""The least time the chip could take for the step's flash-kernel calls over
the time they took. For each kernel: its FLOPs and bytes from its shapes
(benchmarks/flops.py: causal half counted once, the backward's recomputation
counted as the kernel does it), the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s, times the calls the trace counts (so the second
forward of full recomputation is counted as run). At the cells' shapes the
compute bound holds in all three kernels."""

from benchmarks import flops
from benchmarks.layer_metrics.flash_ms import per_kernel


def _flag(flags, name, default=1):
    flags = [str(f) for f in flags]
    return int(flags[flags.index(name) + 1]) if name in flags else default


def kernel_shapes(run):
    """(batch, heads, seq, head_dim) of one device's kernel call: batch rows
    over dp, heads over tp (ops/attention.KernelSharding)."""
    cell = run["cell"]
    flags = cell.traffic["train_flags"]
    tp = _flag(flags, "--global_tp_deg")
    dp = cell.chips // (tp * _flag(flags, "--pp_deg") * _flag(flags, "--global_cp_deg"))
    f = cell.fields
    return (cell.traffic["global_batch"] // dp, f["num_heads"] // tp,
            cell.traffic["seq_length"], f["head_dim"])


def read(run):
    found = per_kernel(run)
    took = sum(s for s, _ in found.values())
    if not took > 0:
        return None
    batch, heads, seq, head_dim = kernel_shapes(run)
    least = 0.0
    for kind, (_, calls) in found.items():
        cost = flops.flash_kernel_cost(kind, batch, heads, seq, head_dim)
        least += calls * flops.least_time_s(cost, run["peak"])[0]
    return 100.0 * least / took

"""The least time the chip could take for the step's attention-kernel work
over the time the kernels took (`flash_ms`'s three families).

jax's two families (flash, splash) are priced by CALL: each kernel's FLOPs and
bytes from its shapes (benchmarks/flops.py: causal half counted once, the
backward's recomputation counted as the kernel does it), the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, times the calls the trace counts
(so the second forward of full recomputation is counted as run, and so is the
call a pipeline stage makes on a fill or drain tick's padding: the share says
how well the kernel runs, `pp_padding_ms` what the padding costs).

Kernels under `gt.attn.core` are priced by the MODEL's work, whatever they are
cut into: the device's attention layers x [2 products for each forward the
trace shows (forward, recomputed forward) + 5 for the backward where it shows
one] over the device's rows of a step at its heads, so the share reads the
same work whatever implements it.

At the cells' shapes the compute bound holds in every kernel."""

from benchmarks import flops
from benchmarks.layer_metrics.flash_ms import core, per_kernel

# the entries of a configuration's `layer_types` whose mixer calls the kernel
SOFTMAX_LAYERS = ("attention", "full_attention")


def _flag(flags, name, default=1):
    flags = [str(f) for f in flags]
    return int(flags[flags.index(name) + 1]) if name in flags else default


def layout(cell):
    """The mix's degrees, {tp, pp, cp, dp, chunks}, from its `train_flags`."""
    flags = cell.traffic["train_flags"]
    out = {"tp": _flag(flags, "--global_tp_deg"), "pp": _flag(flags, "--pp_deg"),
           "cp": _flag(flags, "--global_cp_deg"), "chunks": _flag(flags, "--chunks")}
    out["dp"] = cell.chips // (out["tp"] * out["pp"] * out["cp"])
    return out


def kernel_shapes(run):
    """(batch, heads, seq, head_dim) of one device's kernel call: batch rows
    over dp and over the microbatches (`--chunks`: a pipeline's tick or an
    accumulation's turn runs one), heads over tp (ops/attention.KernelSharding)."""
    cell = run["cell"]
    lay, f = layout(cell), cell.fields
    return (cell.traffic["global_batch"] // lay["dp"] // lay["chunks"], f["num_heads"] // lay["tp"],
            cell.traffic["seq_length"], f["head_dim"])


def softmax_layers(cell):
    """The attention layers one device runs: the configuration's
    `layer_types` that name one among its first `num_layers`, else its depth;
    a stage's share under pp."""
    f = cell.fields
    kinds = f.get("layer_types")
    layers = (sum(k in SOFTMAX_LAYERS for k in kinds[:f["num_layers"]]) if kinds
              else f["num_layers"])
    return layers / layout(cell)["pp"]


def read(run):
    found, scoped = per_kernel(run), core(run)
    took = sum(s for s, _ in found.values()) + sum(s for s, _ in scoped.values())
    if not took > 0:
        return None
    batch, heads, seq, head_dim = kernel_shapes(run)
    least = 0.0
    for kind, (_, calls) in found.items():
        cost = flops.flash_kernel_cost(kind, batch, heads, seq, head_dim)
        least += calls * flops.least_time_s(cost, run["peak"])[0]
    cell = run["cell"]
    rows = cell.traffic["global_batch"] // layout(cell)["dp"]  # the device's rows of a whole step
    forwards = sum(1 for phase in ("fwd", "remat") if scoped[phase][1])
    for kind, times in (("core_fwd", forwards), ("core_bwd", 1 if scoped["bwd"][1] else 0)):
        cost = flops.flash_kernel_cost(kind, rows, heads, seq, head_dim)
        least += times * softmax_layers(cell) * flops.least_time_s(cost, run["peak"])[0]
    return 100.0 * least / took

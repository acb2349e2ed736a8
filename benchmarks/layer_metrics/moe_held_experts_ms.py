"""Device time a step under `gt.moe.experts` where a share of the experts is
held: the grouped matmuls over the rows the held experts were sent (so this
follows the routing: `moe_rows_held_over_even`), megablox's zeroing of the
rows it skips, the casts of the held kernels and SwiGLU, forward,
recomputation and backward. With `moe_held_dispatch_ms` it adds up to
`moe_held_ms`. Device 0, from the trace."""

from benchmarks.layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.ms_or_none(run, moe_held_ms.EXPERTS)

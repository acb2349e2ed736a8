"""`moe_held_dispatch_ms` in the Qwen3-Next cell: device time a step under
`gt.moe.router`, `gt.moe.dispatch` and `gt.moe.combine` of its four routed
halves, a softmax router over 512 experts with 10 a token: 81920 assignments
a block sorted, gathered and combined whatever share of them is held. The
GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.ms_or_none(run, moe_held_ms.AROUND)

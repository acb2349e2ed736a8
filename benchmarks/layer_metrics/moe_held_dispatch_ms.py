"""Device time a step in what surrounds the held experts' matmuls:
`gt.moe.router` (float32 logits, sigmoid, top-k by score + bias, the counts),
`gt.moe.dispatch` (sorting EVERY assignment by expert, gathering its row) and
`gt.moe.combine` (back into token order, the float32 weighted sum), forward,
recomputation and backward. It runs over all tokens x k assignments whatever
share of them is held, so it does not follow the routing; with
`moe_held_experts_ms` it adds up to `moe_held_ms`. Device 0, from the trace."""

from benchmarks.layer_metrics import moe_held_ms


def read(run):
    return moe_held_ms.ms_or_none(run, moe_held_ms.AROUND)

"""Device time a step in what surrounds the experts' matmuls: `gt.moe.router`
(float32 logits, softmax, top-k, the auxiliary terms), `gt.moe.dispatch`
(sorting the assignments by expert, gathering the rows) and `gt.moe.combine`
(back into token order, the weighted sum), forward, recomputation and
backward. With `moe_experts_ms` it adds up to `moe_ms`."""

from benchmarks.layer_metrics import moe_ms


def read(run):
    return moe_ms.ms_or_none(run, "%s|%s|%s" % (moe_ms.ROUTER, moe_ms.DISPATCH, moe_ms.COMBINE))

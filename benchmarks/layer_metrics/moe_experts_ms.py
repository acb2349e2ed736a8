"""Device time a step under `gt.moe.experts`: the grouped matmuls (gate and
up, down), the casts of their kernels and SwiGLU, forward, recomputation and
backward. Device 0, from the trace."""

from benchmarks.layer_metrics import moe_ms


def read(run):
    return moe_ms.ms_or_none(run, moe_ms.EXPERTS)

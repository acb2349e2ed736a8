"""`mlp_roofline` for the EvaByte cell: the least time the chip could take for
the four SwiGLUs' matmuls (one forward and one backward of gate, up and down at
8192 tokens: 3 x 2 x 4096 x 11008 FLOPs a token a layer forward, the backward
twice that, over the chip's peak) over the time the step spent under `gt.mlp`
(`eva_mlp_ms`). A recomputed forward and the activation's passes are in the time
and not in the count, so the share cannot pass 100 %. The same reader as
`mlp_roofline` under a name of its own (`eva_mlp_ms` says why)."""

from benchmarks.layer_metrics import mlp_roofline


def read(run):
    return mlp_roofline.read(run)

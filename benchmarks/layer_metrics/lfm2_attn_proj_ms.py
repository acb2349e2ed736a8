"""`attn_proj_ms` in the LFM2-MoE cell: device time a step under
`gt.attn.proj`, everything of the one attention layer's mixer but the flash
kernels: the q and k/v projections (32 on 8 heads of 64), the two 64-wide
RMSNorms a head, rope on all 64 dims and the output projection, forward,
recomputation and backward. The same reader as `attn_proj_ms` under a name of
its own: an accepted entry lists its own cells and may only have cells
appended."""

from benchmarks.layer_metrics import attn_proj_ms


def read(run):
    return attn_proj_ms.read(run)

"""`attn_proj_ms` in the Laguna cell: device time a step under `gt.attn.proj`,
everything of the two FULL attention layers' mixers but the flash kernels: the
q and k/v projections (48 on 8 heads of 128), yarn's rope on the first 64 of a
head's dims, the per-head gate and the output projection, forward,
recomputation and backward. The window layers' run under `gt.attn.window`
(`window_proj_ms`). The same reader as `attn_proj_ms` under a name of its own:
an accepted entry lists its own cells and may only have cells appended."""

from benchmarks.layer_metrics import attn_proj_ms


def read(run):
    return attn_proj_ms.read(run)

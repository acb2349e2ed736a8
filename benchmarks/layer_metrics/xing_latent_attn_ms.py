"""`latent_attn_ms` in the Xing4.0 cell: device time a step under `gt.attn.latent`, every layer's latent
attention but the flash kernels: the low-rank q (down, norm, up), the compressed k/v down and up with its norm,
rope under yarn on the 64 rotated dims, the padding of q, k and v to the attention call's 256 and `wo`;
forward, recomputation and backward. The GLM cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import latent_attn_ms


def read(run):
    return latent_attn_ms.read(run)

"""`latent_attn_ms` in the Kimi-Linear cell: device time a step under
`gt.attn.latent`, the one latent-attention layer's projections (q a head
straight from the hidden state, the compressed k/v down and up with its norm),
the padding of v to the attention call's width and `wo`; no rope. The GLM
cell's reader, whose entry lists its own cell."""

from benchmarks.layer_metrics import latent_attn_ms


def read(run):
    return latent_attn_ms.read(run)

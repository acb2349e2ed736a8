"""Device time a step under `gt.attn.latent` (models/base.py
`latent_qkv_projection` and the output projection): latent attention's
low-rank projections, their norms, rope, the concatenations and `wo`,
everything of the attention half but the flash kernels (`flash_ms`), in the
stack's layers and in the MTP module's block, forward, recomputation and
backward. Device 0, from the trace. None where the traced program names no
such scope (a model without latent attention; the parent of the PR that
added it)."""

from benchmarks import scopes

LATENT = r"gt\.attn\.latent"


def read(run):
    return scopes.ms_a_step(run, LATENT) or None

"""Device time a step under `gt.attn.ssd` (ops/ssd.py `ssd_scan`): Mamba-2's
scan in every state-space layer, the chunks' decay masks and products, the
state carried from chunk to chunk and the outputs read off it, forward,
recomputation and backward. With `ssm_mixer_ms` it adds up to the state-space
mixers. Device 0, from the trace. None where the traced program names no such
scope (a model without state-space layers; the parent of the PR that added
them)."""

from benchmarks import scopes

SSD = r"gt\.attn\.ssd"


def read(run):
    return scopes.ms_a_step(run, SSD) or None

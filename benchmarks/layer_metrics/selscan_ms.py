"""Device time a step under `gt.attn.selscan` (ops/selective_scan.py
`selective_scan`): Mamba-1's selective scan in every Mamba-1 layer, the chunks'
sums, the states carried from chunk to chunk, the positions' loop and the
outputs read off the states, forward, recomputation and backward. With
`mamba_mixer_ms` it adds up to the Mamba-1 mixers. Device 0, from the trace.
None where the traced program names no such scope (a model without Mamba-1
layers; the parent of the PR that added them)."""

from benchmarks import scopes

SELSCAN = r"gt\.attn\.selscan" + scopes.END


def read(run):
    return scopes.ms_a_step(run, SELSCAN) or None

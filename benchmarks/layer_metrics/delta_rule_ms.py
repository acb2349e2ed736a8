"""Device time a step under `gt.attn.delta` (ops/linear_attention.py
`gated_delta_rule`): the gated delta rule's core in every linear layer, the
chunks' triangular solves, the state carried from chunk to chunk and the
outputs read off it, forward, recomputation and backward. With
`linear_attn_ms` it adds up to the linear mixers. Device 0, from the trace.
None where the traced program names no such scope."""

from benchmarks import scopes

DELTA = r"gt\.attn\.delta"


def read(run):
    return scopes.ms_a_step(run, DELTA) or None

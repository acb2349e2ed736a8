"""Device time a step under `gt.attn.shortconv` (models/parts/conv.py
`conv_mixer`): the two matmuls of every gated short-convolution mixer, hidden
-> [B | C | u] (2048 x 6144) and channels -> hidden (2048 x 2048), forward,
recomputation and backward. With `conv_gate_ms` it adds up to the convolution
mixers: neither scope's name begins the other. A fusion is booked by its
principal op (`trace.origins_from_hlo`: its matmul, else its root), so the
norm before the mixer, where XLA fuses it into the first matmul, is in here.
Device 0, from the trace. None where the traced program names no such scope (a
model without convolution layers; the parent of the PR that added them)."""

from benchmarks import scopes
from benchmarks.layer_metrics.mlp_ms import END

PROJ = r"gt\.attn\.shortconv" + END


def read(run):
    return scopes.ms_a_step(run, PROJ) or None

"""Device time a step under `gt.attn.conv_gate` (models/parts/conv.py
`conv_mixer`): the pass between a gated short-convolution mixer's two matmuls,
`B * u`, the three taps of the causal depthwise convolution
(`ops/linear_attention.causal_conv`: one pad, then slices) and `C * v`, in
every convolution layer, forward, recomputation and backward: XLA's
elementwise fusions, memory bound. With `shortconv_proj_ms` it adds up to the
convolution mixers. Device 0, from the trace. None where the traced program
names no such scope (a model without convolution layers; the parent of the PR
that added them)."""

from benchmarks import scopes
from benchmarks.layer_metrics.mlp_ms import END

GATE = r"gt\.attn\.conv_gate" + END


def read(run):
    return scopes.ms_a_step(run, GATE) or None

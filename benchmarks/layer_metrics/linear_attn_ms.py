"""Device time a step under `gt.attn.linear` (models/base.py `linear_mixer`):
everything of the gated-DeltaNet linear-attention mixers but the delta rule
itself (`delta_rule_ms`): the projections to q, k, v, z and to the gates, the
causal convolution and its SiLU, the L2 norms, the gated RMSNorm and the
output projection, in every linear layer, forward, recomputation and
backward. Device 0, from the trace. None where the traced program names no
such scope (a model without linear layers; the parent of the PR that added
them)."""

from benchmarks import scopes

LINEAR = r"gt\.attn\.linear"


def read(run):
    return scopes.ms_a_step(run, LINEAR) or None

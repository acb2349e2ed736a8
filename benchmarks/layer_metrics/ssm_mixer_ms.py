"""Device time a step under `gt.attn.ssm` (models/base.py `ssm_mixer`):
everything of the Mamba-2 state-space mixers but the scan itself (`ssd_ms`):
the projection to z, x, B, C and dt, the causal convolution with its bias and
SiLU, dt's softplus, the gated RMSNorm over all the mixer's channels and the
output projection, in every state-space layer, forward, recomputation and
backward. Device 0, from the trace. None where the traced program names no
such scope (a model without state-space layers; the parent of the PR that
added them)."""

from benchmarks import scopes

SSM = r"gt\.attn\.ssm"


def read(run):
    return scopes.ms_a_step(run, SSM) or None

"""Device time a step under `gt.attn.mamba` (models/parts/mamba.py
`mamba_mixer`): everything of the Mamba-1 mixers but the scan itself
(`selscan_ms`): the projection to x and z, the causal convolution with its bias
and SiLU, the projections to dt_r, B and C and from dt_r to dt, dt's softplus,
the gate and the output projection, in every Mamba-1 layer, forward,
recomputation and backward. Device 0, from the trace. None where the traced
program names no such scope."""

from benchmarks import scopes

MAMBA = r"gt\.attn\.mamba" + scopes.END


def read(run):
    return scopes.ms_a_step(run, MAMBA) or None

"""Device time a step under `gt.attn.kda_mixer` (models/base.py `kda_mixer`):
everything of the Kimi-Delta-Attention mixers but the rule itself
(`kda_rule_ms`): the projection to q, k, v, the causal convolution and its
SiLU, the L2 norms, the two low-rank pairs (the gate, with its softplus and
exp, and the output gate), beta, the gated RMSNorm and the output projection,
in every KDA layer, forward, recomputation and backward. Device 0, from the
trace. None where the traced program names no such scope."""

from benchmarks.layer_metrics.mlp_ms import END, ms_or_none

MIXER = r"gt\.attn\.kda_mixer" + END


def read(run):
    return ms_or_none(run, MIXER)

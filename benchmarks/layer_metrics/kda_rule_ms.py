"""Device time a step under `gt.attn.kda_rule` (ops/linear_attention.py
`kda_rule`): the per-channel delta rule's core in every KDA layer, the
sub-blocks' decayed products, the chunks' triangular solves, the state carried
from chunk to chunk and the outputs read off it, forward, recomputation and
backward. With `kda_mixer_ms` it adds up to the KDA mixers. Device 0, from the
trace. None where the traced program names no such scope (a model without KDA
layers; the parent of the PR that added them)."""

from benchmarks.layer_metrics.mlp_ms import END, ms_or_none

RULE = r"gt\.attn\.kda_rule" + END


def read(run):
    return ms_or_none(run, RULE)

"""Device time a step under `gt.attn.eva` (models/parts/eva.py `eva_mixer`):
everything of an EVA attention mixer but the pooling (`eva_prep_ms`) and the
aggregation (`eva_agg_ms`): the q, k, v projection, rope on q and k, and the
output projection, forward, recomputation and backward. The scope's name ends
where the other two go on with `_` and a letter (`scopes.END`). Device 0, from the trace.
None where the traced program names no such scope."""

from benchmarks import scopes

PROJ = r"gt\.attn\.eva" + scopes.END


def read(run):
    return scopes.ms_a_step(run, PROJ) or None

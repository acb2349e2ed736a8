"""Device time a step under `gt.attn.eva_agg` (ops/eva_attention.py
`aggregate`): EVA attention's aggregation in every layer, a query's own window
and the pooled keys of earlier windows under one softmax: the two kernels
(`eva_agg_fwd`, `eva_agg_bwd`) or XLA's windows, the rows' `delta` and kept
statistics, forward, recomputation and backward. With `eva_prep_ms` and
`eva_proj_ms` it adds up to the EVA mixers. Device 0, from the trace. None where
the traced program names no such scope (a model without EVA attention layers;
the parent of the PR that added them)."""

from benchmarks import scopes

AGG = r"gt\.attn\.eva_agg" + scopes.END


def read(run):
    return scopes.ms_a_step(run, AGG) or None

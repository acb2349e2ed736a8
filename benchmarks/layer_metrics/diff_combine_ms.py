"""Device time a step under `gt.attn.diff` (models/parts/attention.py
`diff_arranged`, `diff_combined`): differential attention's own arithmetic
around the attention calls in every window, full and cross layer: the heads'
pairing and their padding to the call's width, lambda, the subtraction of the
two maps, the sub-norm over a pair's dims and its factor, forward,
recomputation and backward. The calls themselves are `flash_ms` (full, cross)
and the band's kernels; the projections `phi4_attn_proj_ms`. Device 0, from
the trace. None where the traced program names no such scope."""

from benchmarks import scopes

DIFF = r"gt\.attn\.diff" + scopes.END


def read(run):
    return scopes.ms_a_step(run, DIFF) or None

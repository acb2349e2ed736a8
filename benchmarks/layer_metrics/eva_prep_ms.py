"""Device time a step under `gt.attn.eva_prep` (ops/eva_attention.py
`pooled`): the pooling of each chunk's keys and values into one key and value a
head (the chunk's softmax weights `<phi, k>`, the two weighted sums, `mu`), a
pass over k and v on the vector unit, forward, recomputation and backward (the
backward carries the aggregation's cotangents of the pooled keys and values to
k, v, phi and mu). Device 0, from the trace. None where the traced program
names no such scope."""

from benchmarks import scopes

PREP = r"gt\.attn\.eva_prep" + scopes.END


def read(run):
    return scopes.ms_a_step(run, PREP) or None

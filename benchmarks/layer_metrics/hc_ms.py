"""Device time a step under `gt.hc` (models/parts/hyper.py): ALL of the hyper-connections around both halves
of every layer, forward, recomputation and backward: a half's coefficients (`gt.hc.coef`), the Sinkhorn steps
that make H_res (`gt.hc.sinkhorn`), what it reads out of the n streams and writes back into them (`gt.hc.mix`,
`hc_mix_ms`), and the widening after the embedding and the streams' sum before the final norm; `hc_roofline` is
the share of its least. The three nested scopes add up to it; the halves' own scopes (`gt.attn.*`, `gt.mlp`, `gt.moe.*`) lie
inside none of them. Device 0, from the trace. None where the traced program names no such scope (one residual
stream; the parent of the PR that added it)."""

from benchmarks import scopes

HC = r"gt\.hc"


def nested(word=None):
    """The pattern of `gt.hc`, or of `gt.hc.<word>` nested in it."""
    return HC + (r"\." + word if word else "") + scopes.END


def read(run):
    return scopes.ms_a_step(run, nested()) or None

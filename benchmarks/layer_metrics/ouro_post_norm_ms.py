"""Device time a step under `gt.norm.post` (models/base.layer_forward under `post_norm`): the sandwich norms,
an RMSNorm of each half's OUTPUT before it joins the residual stream, two a layer application, on the vector
unit, forward, recomputation and backward: what the sandwich costs. A fusion is booked by its principal op
(`trace.origins_from_hlo`), so a norm XLA fuses into the matmul before it is that matmul's scope's, not this.
Device 0, from the trace. None where the traced program names no such scope."""

from benchmarks import scopes

NORM_POST = r"gt\.norm\.post" + scopes.END


def read(run):
    return scopes.ms_a_step(run, NORM_POST) or None

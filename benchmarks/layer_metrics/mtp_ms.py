"""Device time a step under the top-level scope `gt.mtp`
(models/base.mtp_logits): the multi-token-prediction module's second
embedding lookup, its two norms, the (2 hidden, hidden) projection and its
block (latent attention, its flash kernels, the shared and the routed
experts), forward, recomputation and backward. Its pass through the head and
its cross entropy run under `gt.head_loss` and count in `head_loss_ms`.
Device 0, from the trace. None where the program names no such scope."""

from benchmarks import scopes

MTP = r"gt\.mtp"


def read(run):
    return scopes.ms_a_step(run, MTP) or None

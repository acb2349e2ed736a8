"""Device time a step under `gt.moe.shared`: the shared expert, a dense
SwiGLU of the experts' width that every token passes beside the routed ones
(models/base.layer_forward), forward, recomputation and backward. Device 0,
from the trace. None where the program names no such scope."""

from benchmarks import scopes

SHARED = r"gt\.moe\.shared"


def read(run):
    return scopes.ms_a_step(run, SHARED) or None

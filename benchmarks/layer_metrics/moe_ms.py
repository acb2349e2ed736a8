"""Device time a step in the routed-experts blocks: every op under a
`gt.moe.*` scope (router, dispatch, experts, combine; ops/moe.py), forward,
recomputation and backward. Device 0, from the trace. None where the
program names no such scope."""

from benchmarks import scopes

MOE = r"gt\.moe\."
ROUTER = r"gt\.moe\.router"
DISPATCH = r"gt\.moe\.dispatch"
EXPERTS = r"gt\.moe\.experts"
COMBINE = r"gt\.moe\.combine"


def ms_or_none(run, pattern):
    """A `gt.moe` scope's milliseconds a step; None where the traced program
    has no routed block (and so nothing to read)."""
    if not scopes.ms_a_step(run, MOE):
        return None
    return scopes.ms_a_step(run, pattern)


def read(run):
    return ms_or_none(run, MOE)

"""Device time a step in the ROUTED half of the expert blocks whose experts
are held here in part: every op under `gt.moe.router`, `gt.moe.dispatch`,
`gt.moe.experts` and `gt.moe.combine` (ops/moe.py), in the stack's routed
layers and the MTP module's block, forward, recomputation and backward. The
shared expert (`gt.moe.shared`) is `moe_shared_ms` and not in here, so the
two add up to everything under `gt.moe.`. `moe_held_experts_ms` and
`moe_held_dispatch_ms` split it by scope (`ms_or_none` below, the pattern its
parameter) and add up to it. Device 0, from the trace. None where the program
names no such scope."""

from benchmarks import scopes

ROUTED = r"gt\.moe\.(router|dispatch|experts|combine)"
EXPERTS = r"gt\.moe\.experts"
AROUND = r"gt\.moe\.(router|dispatch|combine)"


def ms_or_none(run, pattern):
    """A routed scope's milliseconds a step; None where the traced program
    has no routed block."""
    if not scopes.ms_a_step(run, ROUTED):
        return None
    return scopes.ms_a_step(run, pattern)


def read(run):
    return ms_or_none(run, ROUTED)

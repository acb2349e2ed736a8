"""Device time a step under `gt.mlp` (models/base.layer_forward, around its
call of `dense_mlp`): the dense MLP half of every layer that has one, the up
(and gate) projection, the activation and the down projection, forward,
recomputation and backward. The shared expert calls `dense_mlp` too, under
`gt.moe.shared`, and is `moe_shared_ms`, not this: an op carries ONE scope
nested in its layer run's, so a run's parts add up (`layers_rest_ms`). A
fusion is booked by its principal op (`trace.origins_from_hlo`: its matmul,
else its root), so a norm or a residual add that XLA fuses into a matmul of
the MLP is the MLP's. Device 0, from the trace. None where the traced
program names no such scope (no dense MLP; the parent of the PR that named
it)."""

from benchmarks import scopes

END = scopes.END  # where a scope's name ends: `gt.mlp`, not `gt.mlp_in`
MLP = r"gt\.mlp" + END


def both(a, b):
    """The pattern of the labels that match `a` and `b`, in either order."""
    return "^(?=.*%s)(?=.*%s)" % (a, b)


def ms_or_none(run, scope, phase=None):
    """A nested scope's milliseconds a step, or with `phase` (a pattern the
    transforms leave in the label: `scopes.REMAT`) those of that phase alone,
    which may be 0; None where the traced program names no such scope."""
    if not scopes.ms_a_step(run, scope):
        return None
    return scopes.ms_a_step(run, scope if phase is None else both(scope, phase))


def read(run):
    return ms_or_none(run, MLP)

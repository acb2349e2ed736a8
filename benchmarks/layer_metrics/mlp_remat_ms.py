"""Device time a step in recomputing the dense MLP inside the backward
(`--checkpoint 1`): ops under `gt.mlp` AND `checkpoint/rematted_computation`,
the part of `mlp_ms` that `layers_remat_ms` holds. What is recomputed is what
the backward reads: `jax.checkpoint` traces the whole forward again and drops
what nothing uses, so a down projection whose result feeds only the layer's
output is not in here. A fusion is booked by its principal op
(`trace.origins_from_hlo`). Zero where the program has the scope and
recomputes nothing; None where it names no `gt.mlp`."""

from benchmarks import scopes
from benchmarks.layer_metrics import mlp_ms


def read(run):
    return mlp_ms.ms_or_none(run, mlp_ms.MLP, scopes.REMAT)

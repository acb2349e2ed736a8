"""Reading the program's named scopes out of a reduced trace.

The step program names its parts with `jax.named_scope`
(galvatron_tpu/obs/tracing.py): `gt.embed`, `gt.layers.r<k>`, `gt.head_loss`,
`gt.optimizer`, `gt.guard`, `gt.grad_accum`. The name lands in every op's
`op_name`, and `trace._label` puts that beside the instruction with every
character outside `[A-Za-z0-9_.,>/-]` turned into `_`. The transforms wrap
the name, so an op of run 0's forward is labelled `...:jvp_gt.layers.r0_/...`,
of its backward `...:transpose_jvp_gt.layers.r0__/...`, and of its
recomputation inside the backward
`...:transpose_jvp_gt.layers.r0__/while/body/closed_call/checkpoint/rematted_computation/...`.
A fusion is labelled by its principal op (`trace.origins_from_hlo`: its
matmul, else its root), so one that XLA builds across two scopes is booked
to one of them.

The names are typed here once more, on purpose: they are the yardstick's,
and `tests/benchmarks/test_scopes.py` holds them to the program's.
"""

from __future__ import annotations

import re
from typing import Any, Mapping, Optional

from benchmarks.trace import ops_matching

SCOPE = r"gt\.[a-z_]+"
# the transform around a scope's name, as the label carries it
BACKWARD = r"transpose_[a-z_]*"
REMAT = r"rematted_computation"
LAYERS = r"gt\.layers\.r\d+"

LAYERS_FWD = r"^(?!.*%s%s)(?!.*%s).*%s" % (BACKWARD, SCOPE, REMAT, LAYERS)
LAYERS_REMAT = r"%s.*%s" % (LAYERS, REMAT)
LAYERS_BWD = r"^(?!.*%s).*%s%s" % (REMAT, BACKWARD, LAYERS)
EMBED = r"gt\.embed"
HEAD_LOSS = r"gt\.head_loss"
OPTIMIZER = r"gt\.optimizer"
GUARD = r"gt\.guard"
UNSCOPED = r"^(?!.*%s)" % SCOPE


def has_scopes(run: Mapping[str, Any]) -> bool:
    """Whether the run has a trace of a program that names its parts."""
    rx = re.compile(SCOPE)
    return bool(run.get("trace")) and any(rx.search(label) for label in run["trace"]["ops_a_step"])


def ms_a_step(run: Mapping[str, Any], pattern: str) -> Optional[float]:
    """Device 0's milliseconds a step in the ops whose label matches; None
    where there is no trace, or the program has no scopes (the parent of
    the PR that added them). A program with scopes in which nothing matches
    reads 0: a step without the anomaly guard has no `gt.guard`."""
    if not has_scopes(run):
        return None
    return ops_matching(run["trace"], pattern)[0] * 1e3

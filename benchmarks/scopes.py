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
# where a scope's name ends in a label: `gt.mlp/...`, and `gt.mlp_/...` where a
# transform wraps the name itself (`vmap(gt.mlp)` under the pipeline's vmapped
# stage body), but not `gt.mlp_in`, `gt.mlp.in` or `gt.mlpx`
END = r"(?![a-z.]|_[a-z])"
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
# The GPipe schedule (parallel/pipeline.pipeline_apply) is ONE `lax.scan` over
# `chunks + pp - 1` ticks whose body runs every stage's layers, and it stands
# under no scope of its own: its ops are the only ones whose `while` body hangs
# directly off the transform's wrapper (`jvp()/while/body/...`, backward
# `transpose(jvp())/while/body/...`; a layer run's scan is
# `jvp(gt.layers.r<k>)/while/body/...`). The nested scopes (`gt.mlp`,
# `gt.attn.proj`) and the flash kernels' names are inside it as elsewhere
TICK_BODY = r":(?:transpose_)?jvp_/while/body/"
# Stated here BEFORE the program has it: the scope a later PR puts around an
# attention kernel of the repo's own (nested in `gt.layers.r<k>`, as the other
# parts), so that `flash_ms` and `flash_roofline` find the kernel whatever its
# custom calls are named and price it by the MODEL's work
# (layer_metrics/flash_ms.py). No program names it yet; where the program's
# `obs/tracing.py` comes to, it spells it so.
ATTN_CORE = r"gt\.attn\.core" + END


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

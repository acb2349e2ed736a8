"""Which form each part of the step took, said by the code that decides it as
it is traced and heard by whoever records around the trace.

A part of the step that has more than one form (a Pallas kernel or XLA's ops, a
layout, a collective) decides by what it observes, and says so once, beside
the decision: `took(part, form)`. A reader wraps what it traces in
`recording()` and reads `{part: {form: count}}` of what was traced inside it
on its own thread: the trainer around the step's lowering (the `compile`
event's `forms`), a test around its call. Nothing is kept outside a `with`:
what a cached inner trace does not run again is not said again, and a second
lowering in the process reads its own. The part names are constants defined
here and nowhere else, as the scopes are in obs/tracing.py. stdlib only.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Hashable, Iterator, Optional

# ------------------------------------------------------------------- parts
DELTA_RULE = "delta_rule"  # ops/linear_attention.gated_delta_rule, kernel_mixer: "pallas" | "xla" a call
KDA_RULE = "kda_rule"  # ops/linear_attention.kda_rule, kda_kernel_mixer: the per-channel rule's, the same
# the passes around a rule's core (ops/linear_attention.mixer_form, a pass a
# part: a `Layout`'s `counted`), "pallas" | "xla" a mixer
CONV_NORM = "conv_norm"
GATED_NORM = "gated_norm"
KDA_CONV_NORM = "kda_conv_norm"
KDA_GATE = "kda_gate"
KDA_GATED_NORM = "kda_gated_norm"
SHORT_CONV = "short_conv"  # models/parts/conv.conv_mixer: "xla", its one form
# ops/ssd.ssd_scan, a call: "pallas: <groups of B and C> group(s) x <heads a grid step holds> heads a block" (the
# kernels) | "<groups> group(s) x <heads whose masks are alive together> heads at once" (the XLA form)
SSD = "ssd"
SELECTIVE_SCAN = "selective_scan"  # ops/selective_scan.selective_scan: "pallas" | "xla" a call
CAUSAL_ATTENTION = "causal_attention"  # ops/attention.core_attention without a window: "pallas" | "jax_flash" | "xla" a call
WINDOW_ATTENTION = "window_attention"  # ops/attention._windowed: "pallas" | "xla" a call
WINDOW_OPERANDS = "window_operands"  # and, of the first, "as_projected": q unturned with its tables
EVA_ATTENTION = "eva_attention"  # ops/eva_attention.aggregate: "pallas" | "xla" a call
MOE_ROWS = "moe_rows"  # ops/moe._local_moe: `rows_form`'s "kernel" | "xla" a routed block
EXPERT_WINDOW = "expert_window"  # a block of a share: the rows of its window ("0": the whole range)
# ops/moe._local_moe, a block on the megablox kernels: "<kernel> <K>x<N> r<even rows a group>: <tm>x<tk>x<tn>",
# once a distinct call (`ops/moe.gmm_tiling`; K, N: the dims the call's K and N tiles run over)
GMM_TILES = "gmm_tiles"
GATED_KERNEL_GRADS = "gated_kernel_grads"  # models/base._gated_grads_as_stored: "as_stored" a leaf
TABLE_LOOKUP = "table_lookup"  # models/parts/embed_head.vocab_parallel_lookup: "rows_over_dp" | "table_whole"
VOCAB_SPLIT = "vocab_split"  # parallel/pipeline's scan engine: the mesh axes, as "pp,m0"
# models/base.run_layers: "zero_layout" a stacked leaf asked for in ZeRO's; "compute_dtype" a scanned run whose
# cotangents are stacked in the compute dtype (the launch's answer to a device with little room beside the state)
SCAN_GRADS = "scan_grads"
HYPER = "hyper"  # models/parts/hyper.coefficients: "xla", its one form, a half of a hyper-connected layer
# models/base.run_layers, a model with layers of ONE half: "<halves run> of <2 x layers>", once a trace
HALVES = "halves"
MLP_ACTIVATION = "mlp_activation"  # models/parts/mlp.dense_mlp: "written_out" (a GELU) | "folded" into the down matmul, a call


class _Heard(threading.local):
    """The recordings open on a thread, the innermost last: (what it holds, the keys it met)."""

    def __init__(self):
        self.open = []


_heard = _Heard()


class Recording(dict):
    """{part: Counter(form -> count)}. A part that was not heard reads as no
    forms and a form that was not taken as 0, neither stored by the reading."""

    def __missing__(self, part: str) -> collections.Counter:
        return collections.Counter()


def took(part: str, form, key: Optional[Hashable] = None) -> None:
    """`part` was traced in `form` (said as `str(form)`). With a `key` (a leaf's
    path, a layer and a path) a second trace of the same part, form and key is
    the same leaf: a recording counts it once."""
    form = str(form)
    for recorded, seen in _heard.open:
        if key is not None:
            if (part, form, key) in seen:
                continue
            seen.add((part, form, key))
        recorded.setdefault(part, collections.Counter())[form] += 1


@contextlib.contextmanager
def recording() -> Iterator[Recording]:
    """-> the `Recording` of what is traced inside the `with` on this thread,
    one nested in another heard by both."""
    recorded = Recording()
    _heard.open.append((recorded, set()))
    try:
        yield recorded
    finally:
        _heard.open.pop()

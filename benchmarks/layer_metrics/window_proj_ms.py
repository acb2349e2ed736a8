"""Device time a step under `gt.attn.window` (models/parts/window.py
`window_mixer`): everything of a window layer's mixer but the window kernels:
the q and k/v projections at the window layers' own heads (64 on 8 of 128),
rope on all of a head's dims at the window layers' base, the per-head gate's
matmul and product and the output projection, forward, recomputation and
backward, so that a window mixer is this plus `window_attn_ms`. The full
layers' projections run under `gt.attn.proj` (`laguna_attn_proj_ms`): neither
scope's name begins the other. A fusion is booked by its principal op
(`trace.origins_from_hlo`: its matmul, else its root), so the norm before the
mixer, where XLA fuses it into the first matmul, is in here. Device 0, from
the trace. None where the traced program names no such scope (a model without
window layers; the parent of the PR that added them)."""

from benchmarks import scopes
from benchmarks.layer_metrics.mlp_ms import END

PROJ = r"gt\.attn\.window" + END


def read(run):
    return scopes.ms_a_step(run, PROJ) or None

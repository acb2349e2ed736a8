"""Device time a step in the projections of Phi-4-mini-flash's three
attention-like kinds of layer: `gt.attn.proj` (the full differential layer's q,
k, v and output projections with their biases), `gt.attn.window` (the window
layers' same four) and `gt.attn.cross` (a cross layer's q and output
projections: it has no keys or values of its own), forward, recomputation and
backward. Not the attention calls (`flash_ms`, the band) nor the differential
arithmetic (`diff_combine_ms`). Device 0, from the trace. None where the traced
program names no `gt.attn.cross`: the accepted cells' projections are
`attn_proj_ms`'s and `window_proj_ms`'s."""

from benchmarks import scopes

CROSS = r"gt\.attn\.cross" + scopes.END
ALL = r"gt\.attn\.(proj|window|cross)" + scopes.END


def read(run):
    if not scopes.ms_a_step(run, CROSS):
        return None
    return scopes.ms_a_step(run, ALL)

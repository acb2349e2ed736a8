"""Device time a step under `gt.loop` (models/base.looped_states): every pass of a looped stack, all
`num_layers` x `loop_steps` layer applications and the final norm after each pass, forward, recomputation
and backward; what the step spends outside it is the embedding, the heads and their cross entropies, the
exit terms and the update. The loop's scope ENCLOSES the layer runs (`gt.loop/gt.layers.r<k>/...`), so this
is `layers_fwd_ms` + `layers_remat_ms` + `layers_bwd_ms` of the cell (`layers_rest_ms`, which takes any
other `gt.*` in a label for a part of a run, reads 0 there). Device 0, from the trace. None where the traced
program names no such scope (no looped stack; the parent of the PR that added it)."""

from benchmarks import scopes

LOOP = r"gt\.loop" + scopes.END


def read(run):
    return scopes.ms_a_step(run, LOOP) or None

"""Device time a step under `gt.attn.proj` (models/base.attention_mixer
without latent attention): everything of a softmax mixer but the attention
call: the q, k, v projection and its bias, the split-off gate, the heads'
norms, rope, the gate's product and the output projection, forward,
recomputation and backward, so that a softmax mixer is this plus the flash
kernels (`flash_ms`). Latent attention's is `latent_attn_ms`. A fusion is
booked by its principal op (`trace.origins_from_hlo`: its matmul, else its
root), so the norm before the mixer, where XLA fuses it into the q, k, v
matmul, is in here; so is rope's cast of the positions, which jax hoists out
of a scanned run (1 us a step, the one op of a scope outside `gt.layers`).
Device 0, from the trace. None where the traced program
names no such scope."""

from benchmarks.layer_metrics import mlp_ms

PROJ = r"gt\.attn\.proj" + mlp_ms.END


def read(run):
    return mlp_ms.ms_or_none(run, PROJ)

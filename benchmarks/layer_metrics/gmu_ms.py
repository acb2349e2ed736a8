"""Device time a step under `gt.attn.gmu` (models/parts/mamba.py `gmu_mixer`):
the gated memory units, two matmuls and a gate on ANOTHER layer's scan output,
forward, recomputation and backward; the backward's cotangent of the memory
(summed over the readers into the publishing layer's) is booked here too.
Device 0, from the trace. None where the traced program names no such scope."""

from benchmarks import scopes

GMU = r"gt\.attn\.gmu" + scopes.END


def read(run):
    return scopes.ms_a_step(run, GMU) or None

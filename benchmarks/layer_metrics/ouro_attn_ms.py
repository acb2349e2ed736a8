"""Device time a step in the causal-attention kernels under `gt.attn.core` of a LOOPED stack: the pair of
`ops/causal_attention.py` in every layer application (`num_layers` x `loop_steps` forwards, as many
recomputed forwards under `--checkpoint 1`, as many backwards). `flash_ms`'s third family by its own
patterns (`flash_ms.core`: a Pallas call under the scope, by phase), under a name of its own: the standing
`flash_roofline` prices `num_layers` layers and would read `loop_steps` times too high in a looped cell, so
this cell stays off that pair's lists. None where no such call is traced, or the model is not looped."""

from benchmarks.layer_metrics.flash_ms import core


def phases(run):
    """{phase: (seconds a step, calls a step)} of the kernels, or None where the cell is not looped."""
    cell = run.get("cell")
    if cell is None or cell.fields.get("loop_steps", 1) < 2 or not run.get("trace"):
        return None
    return core(run)


def read(run):
    found = phases(run)
    if not found or not any(calls for _, calls in found.values()):
        return None
    return sum(s for s, _ in found.values()) * 1e3

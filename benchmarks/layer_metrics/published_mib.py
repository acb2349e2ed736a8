"""MiB of the tensors the step's layers PUBLISH for later layers beside the
residual stream: the program's own counter `published_mib` of the telemetry
`step` event (models/base.py `run_layers`: a Mamba-1 layer's memory and a full
differential layer's keys and values, as handed on, summed over the step's
microbatches), mean over the steps of `window_steps`. What outlives its layer
and full recomputation cannot drop: outputs of ONE checkpointed layer, inputs
of every reader. None where the program emits no such field."""

from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter

FIELD = "published_mib"


def read(run):
    return step_counter(run, FIELD)

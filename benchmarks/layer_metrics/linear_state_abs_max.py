"""The largest magnitude in any head's final state of any linear layer: the
program's own counter `linear_state_abs_max` of the telemetry `step` event
(models/base.py `linear_mixer`, the worst layer's, fetched with the loss),
mean over the steps of `window_steps`. The delta rule's blow-up alarm: with
L2-normalised keys and beta under 1 a state stays of the order of the
values; one that grows a step after step says the recurrence has left its
stable range. None where the program emits no such field."""

from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter

FIELD = "linear_state_abs_max"


def read(run):
    return step_counter(run, FIELD)

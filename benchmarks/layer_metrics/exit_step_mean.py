"""The mean pass a position exits at under the exit gate's distribution, `sum_t t p_t` (1 .. `loop_steps`):
the program's own counter `exit_step_mean` of the telemetry `step` event (models/parts/loop.expected_loss;
fetched with the loss), mean over the steps of `window_steps`. A health reading, not a target: about 1.9 of
4 on untrained weights (p near 1/2, 1/4, 1/8, 1/8), and whether the window's gate still spreads its mass over
the passes (1.0 or 4.0: it has collapsed onto one). None where the program emits no such field."""

from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter

FIELD = "exit_step_mean"


def read(run):
    return step_counter(run, FIELD)

"""The largest magnitude of any (channel, state) of a Mamba-1 layer's scan at
any chunk's end: the program's own counter `selscan_state_abs_max` of the
telemetry `step` event (models/parts/mamba.py `mamba_mixer`, the worst layer's,
fetched with the loss), mean over the steps of `window_steps`. The scan's
blow-up alarm: with A < 0 a state is a decaying sum of dt x B and stays of the
order of the inputs; one that grows step after step says the decay has left its
range. None where the program emits no such field."""

from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter

FIELD = "selscan_state_abs_max"


def read(run):
    return step_counter(run, FIELD)

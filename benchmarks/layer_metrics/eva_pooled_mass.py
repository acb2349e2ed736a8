"""The share of the softmax's mass that falls on POOLED keys in an EVA
attention layer: the program's own counter `eva_pooled_mass` of the telemetry
`step` event (models/parts/eva.py `eva_mixer`: the mean over the layers, the
heads and the queries past the first window, read off the two partial sums the
aggregation holds; fetched with the loss), mean over the steps of
`window_steps`. A health reading, not a target: about the share of a query's
keys that are pooled on untrained weights (192 of 1217 at 8192 positions), and
what far context the model has learned to use later. None where the program
emits no such field."""

from benchmarks.layer_metrics.moe_rows_held_over_even import step_counter

FIELD = "eva_pooled_mass"


def read(run):
    return step_counter(run, FIELD)

"""The fullest expert's tokens over the mean, over ALL the experts the router
ranks (held here or not), worst routed block: the program's counter
`expert_load_max_over_mean` of the telemetry `step` event, mean over the
steps of `window_steps`. 1 is even routing; experts / experts a token (16
here) is every token of a block picking the same experts. Beside
`moe_rows_held_over_even` it tells an uneven load from one that has left the
held experts. None where the program emits no such field."""

from benchmarks.layer_metrics import moe_rows_held_over_even


def read(run):
    return moe_rows_held_over_even.step_counter(run, "expert_load_max_over_mean")

"""The rows a step sent through the grouped matmuls of the experts held here
over the even share (tokens x experts a token x held / experts, a routed
block): the program's own counter `expert_rows_held_over_even` of the
telemetry `step` event, fetched with the loss, mean over the steps of
`window_steps`. 1 is what the model FLOPs (and `mfu`) count; on untrained
weights it follows the seed. None where the program emits no such field."""

FIELD = "expert_rows_held_over_even"


def step_counter(run, field):
    """Mean of a `step` event field over the steps of `window_steps`, or None."""
    first, last = run["window_steps"]
    seen = [e[field] for e in run.get("events") or []
            if e.get("type") == "step" and first <= e.get("iter", -1) < last
            and e.get(field) is not None]
    return sum(seen) / len(seen) if seen else None


def read(run):
    return step_counter(run, FIELD)

"""The fullest expert's tokens over the mean, worst layer: the program's own
counter, fetched with the loss (`expert_load_max_over_mean` of the telemetry
`step` event), mean over the steps of `window_steps`. 1 is perfectly even
routing; the grouped matmul's groups are this uneven. None where the program
emits no such field."""


def read(run):
    first, last = run["window_steps"]
    load = [e["expert_load_max_over_mean"] for e in run["events"]
            if e.get("type") == "step" and first <= e.get("iter", -1) < last
            and e.get("expert_load_max_over_mean") is not None]
    return sum(load) / len(load) if load else None

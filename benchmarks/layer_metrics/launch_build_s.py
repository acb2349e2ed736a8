"""From the trainer's entry to a built step function, without the state:
`gt/launch/plan` (model and strategy from the arguments, the strategy lint,
FLOPs) plus `gt/launch/build` (the model, the optimizer, the step function) of
the summary's `launch_ms`."""


def read(run):
    ms = run["summary"].get("launch_ms") or {}
    if "gt/launch/plan" not in ms or "gt/launch/build" not in ms:
        return None
    return (ms["gt/launch/plan"] + ms["gt/launch/build"]) / 1e3

"""Tracing the train step to a jaxpr (`step_fn.trace(*args)`):
`launch_ms["gt/compile/trace"]` of the trainer's summary. With `step_lower_s`
it is the summary's `trace_ms`."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/compile/trace")
    return None if ms is None else ms / 1e3

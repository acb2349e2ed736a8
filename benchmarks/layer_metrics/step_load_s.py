"""Getting the step's executable: the persistent cache's read on a hit, XLA
on a miss. `launch_ms["gt/compile/load"]` of the trainer's summary; with
`step_key_s` it is the summary's `compile_ms`."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/compile/load")
    return None if ms is None else ms / 1e3

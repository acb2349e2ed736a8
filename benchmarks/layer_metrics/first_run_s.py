"""From the compiled step in hand to the first step's drain: the program's
load onto the device, what the initialisers still owed, the first execution,
and the dispatches and `on_step` calls of the steps sent behind it.
`launch_ms["gt/launch/first_run"]` of the trainer's summary."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/launch/first_run")
    return None if ms is None else ms / 1e3

"""Initialising the parameters and the optimizer's state: `init_params` and
`init_opt_state` on the host's clock, their compilations or cache reads in
it (what the device still owes falls into `first_run_s`).
`launch_ms["gt/launch/init_state"]` of the trainer's summary."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/launch/init_state")
    return None if ms is None else ms / 1e3

"""The key of the in-process memo of compiled steps: `as_text()` of the whole
lowered module and its sha256. `launch_ms["gt/compile/key"]` of the trainer's
summary; with `step_load_s` it is the summary's `compile_ms`."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/compile/key")
    return None if ms is None else ms / 1e3

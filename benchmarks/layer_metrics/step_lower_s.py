"""Lowering the traced step to MLIR (`.lower()`), every Pallas body's
Mosaic lowering in it: `launch_ms["gt/compile/lower"]` of the trainer's
summary. With `step_trace_s` it is the summary's `trace_ms`."""


def read(run):
    ms = (run["summary"].get("launch_ms") or {}).get("gt/compile/lower")
    return None if ms is None else ms / 1e3

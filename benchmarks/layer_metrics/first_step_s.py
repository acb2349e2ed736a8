"""Tracing plus compiling (or reading from the persistent cache) the train
step: `trace_ms + compile_ms` of the trainer's summary."""


def read(run):
    s = run["summary"]
    if "trace_ms" not in s or "compile_ms" not in s:
        return None
    return (s["trace_ms"] + s["compile_ms"]) / 1e3

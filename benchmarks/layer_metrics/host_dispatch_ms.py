"""Host time to hand one step to the device (`RuntimeProfiler.dispatch_ms`),
mean over the window's steps, from the trainer's telemetry `step` events."""


def read(run):
    first, last = run["window_steps"]
    ms = [e["dispatch_ms"] for e in run["events"]
          if e.get("type") == "step" and first <= e.get("iter", -1) < last
          and e.get("dispatch_ms") is not None]
    return sum(ms) / len(ms) if ms else None

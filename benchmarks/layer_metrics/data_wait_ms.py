"""Host time the loop waited in `next_batch()` for a step (the `gt/next_batch`
span, `data_wait_ms` of the telemetry `step` event), mean over the steps of
`window_steps`, as host_dispatch_ms reads `dispatch_ms`."""


def read(run):
    first, last = run["window_steps"]
    ms = [e["data_wait_ms"] for e in run["events"]
          if e.get("type") == "step" and first <= e.get("iter", -1) < last
          and e.get("data_wait_ms") is not None]
    return sum(ms) / len(ms) if ms else None

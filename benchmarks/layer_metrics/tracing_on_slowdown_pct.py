"""What the profiler and the telemetry sink cost the step's pace while they
are on: 100 x (median stamp interval of the traced tail / the window's median
step - 1). `--trace 2` only, where one run holds both an untraced window and
a traced tail; None otherwise. The tail's first three intervals are left
out: the first holds the profiler's start, and the loop, which keeps two
steps in flight, needs two more before a stamp waits a whole device step
again."""

import statistics

REFILL = 3


def read(run):
    tail = (run.get("tail_intervals_s") or [])[REFILL:]
    if not tail:
        return None
    return 100.0 * (statistics.median(tail) / run["window"]["median_step_s"] - 1.0)

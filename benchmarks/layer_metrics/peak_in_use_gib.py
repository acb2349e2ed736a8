"""`memory_stats()["peak_bytes_in_use"]` after the window, the fullest chip.
It does not count the step program's temporaries (PERF.md section 7), so it
reads under step_hbm_gib: a per-layer metric only."""


def read(run):
    return run["device"]["memory_peak_bytes"] / 2.0 ** 30

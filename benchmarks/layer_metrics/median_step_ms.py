"""Median of the window's per-step intervals between `on_step` stamps: the
steady step, which one stall in the window does not move."""


def read(run):
    return run["window"]["median_step_s"] * 1e3

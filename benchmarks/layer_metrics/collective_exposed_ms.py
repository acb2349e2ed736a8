"""The part of collective_ms during which no other op runs on that device."""


def read(run):
    return run["trace"]["collective_exposed_s_a_step"] * 1e3

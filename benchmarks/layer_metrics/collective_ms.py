"""Device time a step in all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all ops, device 0, from the trace."""


def read(run):
    return run["trace"]["collective_s_a_step"] * 1e3

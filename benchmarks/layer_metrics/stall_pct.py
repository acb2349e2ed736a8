"""100 x (1 - the window's rate / the rate of its median step): what share of
the window went to stalls. Zero when every step takes the median step's time."""


def read(run):
    return run["window"]["stall_pct"]

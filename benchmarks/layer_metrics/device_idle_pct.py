"""100 x (1 - the union of the device's op intervals over the traced steps),
averaged over the chips."""


def read(run):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

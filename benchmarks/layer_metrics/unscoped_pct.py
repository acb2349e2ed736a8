"""100 x the device time of the ops whose label carries no `gt.` scope over
the time of all ops, device 0: what the named scopes do not account for.
It keeps the scopes honest after a refactor."""

from benchmarks import scopes
from benchmarks.trace import ops_matching


def read(run):
    outside = scopes.ms_a_step(run, scopes.UNSCOPED)
    if outside is None:
        return None
    return 100.0 * outside / (ops_matching(run["trace"], "")[0] * 1e3)

"""Device time a step under `gt.hc.mix` (models/parts/hyper.read, write, widen, contract): what every half
reads out of the n residual streams (`sum_j H_pre[j] X[j]`) and writes back into them (`H_res X + H_post o`),
forward, recomputation and backward, and the widening after the embedding and the streams' sum before the
final norm: memory-bound passes over the (tokens, n x hidden) array. A fusion XLA builds across this scope and
`gt.hc.coef` is booked to one of them by its principal op, so the two read what the compiler's fusions make of
them and their SUM is the steady number (`hc_roofline` is held over `hc_ms` whole for that reason); `hc_ms` less
this bounds the coefficients and the Sinkhorn steps. Device 0, from the trace. None where the traced program
names no such scope."""

from benchmarks import scopes
from benchmarks.layer_metrics.hc_ms import nested


def read(run):
    return scopes.ms_a_step(run, nested("mix")) or None

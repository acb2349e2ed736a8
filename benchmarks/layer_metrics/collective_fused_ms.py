"""Device time a step in the collectives `collective_ms` does not read: the
rows of the program's census (benchmarks/census.py) that are not hidden in a
matmul and whose op the trace names otherwise than `trace.COLLECTIVE` knows:
the fusions that call `%all-reduce-scatter` (a sum and the slice of this
chip's shard), the `async-collective-start.N` / `-done.N` fusions, a
`shard_map`'s `all_to_all.N` and `psum_invariant.N`. With `collective_ms` it
is all the device time in collectives that are ops of their own. Device 0,
from the trace. None on one chip, and for a program that counts nothing."""

from benchmarks import census


def read(run):
    return census.ms(run, lambda row, label: not census.named(label))

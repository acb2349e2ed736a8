"""GiB a chip sends a step in all the step's collectives, the hidden ones too:
each row of the program's census (benchmarks/census.py) at its `wire_bytes`
(a ring's count over its group: all-reduce 2 (g - 1) / g x the operand,
reduce-scatter and all-to-all (g - 1) / g, all-gather g - 1, a permute 1)
times its instruction's calls a step in the trace. The volume the search's
cost model predicts by axis. None on one chip, and for a program that counts
nothing."""

from benchmarks import census


def read(run):
    sent = census.wire_bytes(run)
    return None if sent is None else sent / 2.0 ** 30

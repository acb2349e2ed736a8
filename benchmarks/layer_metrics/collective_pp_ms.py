"""Device time a step in the collectives that run over the pp axis alone
(role `pp` of the program's census, benchmarks/census.py: the stage-to-stage
sends, `collective-permute-start.N` / `-done.N`, and the sums across stages),
the ones hidden in a matmul left out. Device 0, from the trace. None on one
chip, and for a program that counts nothing; 0 without a pipeline."""

from benchmarks import census


def read(run):
    return census.role_ms(run, "pp")

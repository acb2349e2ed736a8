"""Device time a step in the collectives that run over the layers' dp axes
alone (role `dp` of the program's census, benchmarks/census.py: ZeRO's
parameter gathers and gradient reduce-scatters, the table's rows over dp),
whatever the trace names them, the ones hidden in a matmul left out. Device
0, from the trace. None on one chip, and for a program that counts nothing."""

from benchmarks import census


def read(run):
    return census.role_ms(run, "dp")

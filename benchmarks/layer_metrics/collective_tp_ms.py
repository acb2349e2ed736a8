"""Device time a step in the collectives that run over the layers' tp axes
alone (role `tp` of the program's census, benchmarks/census.py: the
activations' gathers and sums, the attention's all-to-alls, the vocabulary's
sums; in the scan pipeline the vocabulary's tp spans pp), whatever the trace
names them, the ones hidden in a matmul left out. Device 0, from the trace.
None on one chip, and for a program that counts nothing."""

from benchmarks import census


def read(run):
    return census.role_ms(run, "tp")

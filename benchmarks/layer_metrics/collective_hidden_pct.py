"""The share of the step's wire bytes (collective_wire_gib) in collectives
the compiler carried inside a matmul's fusion (form `hidden` of the program's
census, benchmarks/census.py: `%async_collective_fusion.N`): what of the
step's traffic is overlapped with compute by construction. It says the
collective shares a fusion with a matmul, not that the matmul was long
enough to cover it. None on one chip, for a program that counts nothing, and
for a step that sends nothing."""

from benchmarks import census


def read(run):
    sent = census.wire_bytes(run)
    if not sent:
        return None
    return 100.0 * census.wire_bytes(run, lambda row: row["form"] == "hidden") / sent

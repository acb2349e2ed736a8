"""Device time a step under `gt.param_gather`: ZeRO-2's compute-dtype copy of
the parameters it stores split over dp, the cast of each chip's float32 shard
and the all-gather over dp of the result (runtime/model_api.compute_params).
Device 0, from the trace. None where the traced program makes no such copy:
one chip, `ddp`, or a program from before the scope."""

from benchmarks import scopes

PARAM_GATHER = r"gt\.param_gather"


def read(run):
    return scopes.ms_a_step(run, PARAM_GATHER) or None

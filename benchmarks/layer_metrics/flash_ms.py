"""Device time a step in the three flash-attention kernels (forward, dkv,
dq), device 0, from the trace. The kernels are found by the names their
custom calls carry in the compiled step (jax's
`pallas.ops.tpu.flash_attention`): `flash_attention.N`, and
`flash_mha_bwd_dkv_...` and `flash_mha_bwd_dq_...` with their block sizes."""

from benchmarks.trace import ops_matching

KERNELS = {
    "fwd": r"^flash_attention[.:]",
    "dkv": r"^flash_mha_bwd_dkv",
    "dq": r"^flash_mha_bwd_dq",
}


def per_kernel(run):
    """{kind: (seconds a step, calls a step)}"""
    return {kind: ops_matching(run["trace"], rx) for kind, rx in KERNELS.items()}


def read(run):
    found = per_kernel(run)
    if not any(calls for _, calls in found.values()):
        return None
    return sum(s for s, _ in found.values()) * 1e3

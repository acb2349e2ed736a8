"""Device time a step in the two window-attention kernels (forward,
backward) of the window layers, device 0, from the trace. The kernels are
found by the names their custom calls carry in the compiled step
(`ops/window_attention.py`: `window_attn_fwd.N`, `window_attn_bwd.N`), none
of which `flash_ms`'s patterns match: the full layers' flash kernels are that
metric's. None where the traced program runs no such kernel (a model without
window layers; a window layer in XLA's form; the parent of the PR that added
them)."""

from benchmarks.trace import ops_matching

KERNELS = {
    "fwd": r"^window_attn_fwd",
    "bwd": r"^window_attn_bwd",
}


def per_kernel(run):
    """{kind: (seconds a step, calls a step)}"""
    return {kind: ops_matching(run["trace"], rx) for kind, rx in KERNELS.items()}


def read(run):
    if not run.get("trace"):
        return None
    found = per_kernel(run)
    if not any(calls for _, calls in found.values()):
        return None
    return sum(s for s, _ in found.values()) * 1e3

"""Device time a step in the attention kernels of the softmax layers (forward,
dkv, dq), device 0, from the trace. Three families are found:

- jax's `pallas.ops.tpu.flash_attention`, by the names its custom calls carry
  in the compiled step: `flash_attention.N`, and `flash_mha_bwd_dkv_...` and
  `flash_mha_bwd_dq_...` with their block sizes (what `ops/attention.py` calls
  today);
- jax's splash kernels (`pallas.ops.tpu.splash_attention`,
  `get_kernel_name`): `splash_mha_fwd_residuals.N`, `splash_mqa_dkv_no_residuals.N`,
  `splash_mha_dq_segmented_no_residuals.N`, ...;
- any Pallas call under the scope `gt.attn.core` (`scopes.ATTN_CORE`: a kernel
  of the repo's own, whatever its calls are named), by phase: forward,
  recomputed forward, backward.

A call of jax's names that runs under `gt.attn.core` is the third family's and
not counted twice. The band kernels of window layers (`window_attn_*`) are none
of these: `window_attn_ms` reads them."""

from benchmarks import scopes
from benchmarks.trace import ops_matching

NOT_CORE = r"^(?!.*%s)" % scopes.ATTN_CORE
KERNELS = {
    "fwd": NOT_CORE + r"(?:flash_attention[.:]|splash_m[hq]a_fwd)",
    "dkv": NOT_CORE + r"(?:flash_mha_bwd_dkv|splash_m[hq]a_dkv)",
    "dq": NOT_CORE + r"(?:flash_mha_bwd_dq|splash_m[hq]a_dq)",
}
# a Pallas call under the scope: the label ends with the primitive's name and
# begins with the kernel's (`pallas_call(name=)`). An op of XLA's own that
# inherits the call's `op_name` (the `copy` of a kernel's output into another
# layout) is named by its opcode and is not the kernel
XLA_OWN = ("bitcast|broadcast|concatenate|convert|copy|custom-call|dynamic-slice|dynamic-update-slice|"
           "fusion|get-tuple-element|pad|reduce|reshape|select|slice|transpose|tuple")
_CORE_CALL = r"^(?!(?:%s)(?:[.\-][\w.\-]*)?:)(?=.*%s)%%s.*pallas_call$" % (XLA_OWN, scopes.ATTN_CORE)
# the transforms' wrappers tell the phases apart, as they do a layer run's: the
# backward's around a scope's name (`transpose(jvp(gt.layers.r0))`) or around
# none (the pipeline's tick scan: `transpose(jvp())`); a `transpose` primitive
# of the forward is followed by no `jvp`
_BACKWARD = r".*transpose_[a-z_]*jvp_"
_REMAT = r".*" + scopes.REMAT
CORE_PHASES = {
    "fwd": _CORE_CALL % ("(?!%s)(?!%s)" % (_BACKWARD, _REMAT)),
    "remat": _CORE_CALL % ("(?=%s)" % _REMAT),
    "bwd": _CORE_CALL % ("(?=%s)(?!%s)" % (_BACKWARD, _REMAT)),
}


def per_kernel(run):
    """{kind: (seconds a step, calls a step)} of jax's two families."""
    return {kind: ops_matching(run["trace"], rx) for kind, rx in KERNELS.items()}


def core(run):
    """{phase: (seconds a step, calls a step)} of the kernels under `gt.attn.core`."""
    return {phase: ops_matching(run["trace"], rx) for phase, rx in CORE_PHASES.items()}


def read(run):
    found = list(per_kernel(run).values()) + list(core(run).values())
    if not any(calls for _, calls in found):
        return None
    return sum(s for s, _ in found) * 1e3

"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the delta rules' cores and the passes around them: a Qwen3-Next linear layer and a Kimi KDA layer at the cells' widths."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import _calls, v5e_2x2  # noqa: F401  (the fixture)


def _count_instructions(hlo):
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", hlo, re.M))


_DELTA_RULE_INSTRUCTIONS = {}  # impl -> its optimised module's: the kernel case reads the XLA case's


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_delta_rule_keeps_a_state_a_chunk_and_runs_on_the_mxu_on_v5e(v5e_2x2, impl):
    """The gated delta rule's core at the Qwen3-Next cell's widths (8192
    tokens, 16 key heads serving 32 value heads, 128 x 128 states), forward
    and backward, for a described v5e: no array of tokens x heads x d_k x d_v
    is ever formed (the recurrence token by token would keep one for its
    backward): the largest is the chunks' starting states. The XLA form: 64
    tokens a chunk; the chunks' products are matmuls and the state is carried
    by a loop. The kernel form (what the chip takes): the custom calls are
    there by their names, no loop and no matmul is left to XLA, and the
    optimised module holds under a tenth of the XLA form's instructions."""
    from galvatron_tpu.ops import linear_attention as L

    tokens, hk, hv, dk, dv = 8192, 16, 32, 128, 128
    chip = SingleDeviceSharding(v5e_2x2[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731
    operands = (sds((1, tokens, hk, dk), jnp.bfloat16), sds((1, tokens, hk, dk), jnp.bfloat16),
                sds((1, tokens, hv, dv), jnp.bfloat16), sds((1, tokens, hv), jnp.float32),
                sds((1, tokens, hv), jnp.float32))

    def compiled_with(form):
        def loss(*ops):
            o, state = L.gated_delta_rule(*ops, impl=form)
            return jnp.sum(o.astype(jnp.float32)) + jnp.max(jnp.abs(state))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*operands).compile()

    compiled = compiled_with(impl)
    hlo = compiled.as_text()
    instructions = _DELTA_RULE_INSTRUCTIONS[impl] = _count_instructions(hlo)
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", hlo)]
    chunk = L.CHUNK if impl == "xla" else L.TILE
    assert max(sizes) == tokens // chunk * hv * dk * dv  # the kept chunk-start states
    assert max(sizes) * chunk == tokens * hv * dk * dv
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1.0 * 2**30  # all heads at once: 2.3 GiB
    dots = len(re.findall(r" (?:dot|convolution)\(", hlo))
    if impl == "xla":
        assert " while(" in hlo and dots >= 20 and "tpu_custom_call" not in hlo
        return
    assert hlo.count("tpu_custom_call") == 2 and " while(" not in hlo and dots == 0
    for name in ("gdn_fwd", "gdn_bwd"):  # what a trace's op table will show
        assert len(re.findall(r'op_name="[^"]*%s' % name, hlo)) >= 1, name
    assert instructions * 10 < (_DELTA_RULE_INSTRUCTIONS.get("xla")
                                or _count_instructions(compiled_with("xla").as_text()))


@pytest.fixture(scope="module")
def qwen3_next_linear_layer(v5e_2x2):
    """One linear layer's mixer of the Qwen3-Next cell (8192 tokens, hidden
    2048, 16 key heads serving 32 value heads of 128, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip:
    -> (the optimised module's text, the forms its parts took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.linear import linear_mixer
    from galvatron_tpu.models.qwen3_next import qwen3_next_config

    tokens = 8192
    cfg = qwen3_next_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(cfg.layer_kinds()[0])
    chip = SingleDeviceSharding(v5e_2x2[0])
    # a mesh of the one described chip says where the operands lie (the
    # default backend here is the CPU)
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"linear": shapes["linear"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def loss(p, y):
        mixer = jax.checkpoint(lambda p, y: linear_mixer(p, y, None, lcfg, attn_sharding=where))
        out, _, counters = mixer(p, y)
        return jnp.sum(out.astype(jnp.float32)) + counters["state_abs_max"]

    with forms.recording() as took:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, took


def test_the_linear_layers_surround_is_lane_aligned_passes_on_v5e(qwen3_next_linear_layer):
    """Between the two projections and the core a linear layer runs as
    Pallas passes over (tokens, channels) arrays, a head a block of 128
    lanes (ops/linear_attention.py): the four kernels are there under
    `gt.attn.linear` by their names, the core's two still under
    `gt.attn.delta`, and what the XLA form cost on a TPU's 8 x 128 tiling
    (PERF.md, PR 38) is gone from that scope: no view of the activations by
    (tokens, heads, 128) at all, so no norm's scale broadcast to full size,
    no physical reshape or relayout copy of a float32 (tokens, 4096) or
    (tokens, 2048) array; no slice of the projection's output written out
    and no padded parts of its cotangent summed."""
    from galvatron_tpu.obs import tracing

    text, took = qwen3_next_linear_layer
    assert took == {forms.CONV_NORM: {"pallas": 1}, forms.GATED_NORM: {"pallas": 1}, forms.DELTA_RULE: {"pallas": 1}}
    tokens, keys = 8192, 2048

    calls = functools.partial(_calls, text)
    # a call each for q, k and v; the forward and its recomputation are one here (no scan between them)
    assert calls("conv_norm_fwd", tracing.ATTN_LINEAR) == 3 and calls("conv_norm_bwd", tracing.ATTN_LINEAR) == 3
    assert calls("gated_norm_fwd", tracing.ATTN_LINEAR) == 1 and calls("gated_norm_bwd", tracing.ATTN_LINEAR) == 1
    assert calls("gdn_fwd", tracing.ATTN_DELTA) == 1 and calls("gdn_bwd", tracing.ATTN_DELTA) == 1
    assert text.count("tpu_custom_call") == 10
    for kernel in ("conv_norm", "gated_norm"):  # never under the core's scope, whose roofline reads it alone
        assert not calls(kernel + "_fwd", tracing.ATTN_DELTA) and not calls(kernel + "_bwd", tracing.ATTN_DELTA)
    assert not re.search(r"\[(?:1,)?%d,(?:32|16),128\]" % tokens, text)  # no view by heads
    offenders = []
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or tracing.ATTN_LINEAR not in line:
            continue
        name, result, kind = found.groups()
        # the result's arrays over all tokens, at least (tokens, 2048) large: activations, not weights
        over_tokens = {dtype for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]*)\]", result)
                       if str(tokens) in dims.split(",")
                       and np.prod([int(d) for d in dims.split(",")]) >= tokens * keys}
        if (("f32" in over_tokens and kind in ("reshape", "copy", "transpose", "broadcast"))
                or (over_tokens and kind in ("slice", "dynamic-slice", "pad", "concatenate"))):
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)


@pytest.fixture(scope="module")
def kimi_kda_layer(v5e_2x2):
    """One Kimi-Delta-Attention mixer at the Kimi-Linear cell's widths (8192
    tokens, hidden 2304, 32 heads of 128, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip:
    -> (the optimised module's text, the forms its core and its passes took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.kimi_linear import kimi_linear_config
    from galvatron_tpu.models.parts.kda import kda_mixer

    tokens = 8192
    cfg = kimi_linear_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("kda")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"kda": shapes["kda"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def loss(p, y):
        mixer = jax.checkpoint(lambda p, y: kda_mixer(p, y, None, lcfg, attn_sharding=where))
        out, _, counters = mixer(p, y)
        return jnp.sum(out.astype(jnp.float32)) + counters["state_abs_max"]

    with forms.recording() as took:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, took


def test_the_kda_layers_core_is_two_kernels_once_each_on_v5e(kimi_kda_layer):
    """The per-channel rule's core on a TPU: `kda_fwd` and `kda_bwd` under
    `gt.attn.kda_rule`, ONCE each under the layer's `jax.checkpoint` (the
    rule keeps its own residuals: the backward does not run the forward again;
    the first forward and the recomputation are one here, no scan between
    them), nothing of them under the surround's scope, which its own readers
    read, no other kernel under the core's (its roofline divides a fixed cost
    by all that scope holds), and no view of the activations by (tokens, 32,
    128) under it: a head is a block of 128 lanes of a (tokens, 4096) array."""
    from galvatron_tpu.obs import tracing

    text, took = kimi_kda_layer
    assert took[forms.KDA_RULE] == {"pallas": 1}
    tokens = 8192
    assert _calls(text, "kda_fwd", tracing.ATTN_KDA_RULE) == 1 and _calls(text, "kda_bwd", tracing.ATTN_KDA_RULE) == 1
    assert len(re.findall(r'custom-call\(.*op_name="[^"]*%s/' % re.escape(tracing.ATTN_KDA_RULE), text)) == 2
    assert not _calls(text, "kda_fwd", tracing.ATTN_KDA) and not _calls(text, "kda_bwd", tracing.ATTN_KDA)
    for line in text.splitlines():
        if tracing.ATTN_KDA_RULE in line:
            assert not re.search(r"\[(?:1,)?%d,32,128\]" % tokens, line), line[:200]


def test_the_kda_layers_surround_is_lane_aligned_passes_on_v5e(kimi_kda_layer):
    """Between its projections and the core a Kimi-Delta-Attention layer runs
    as Pallas passes over (tokens, channels) arrays, a head a block of 128
    lanes (ops/linear_attention.py: the linear layers' kernels under another
    `Layout`, and the per-channel gate's pair): every pass is there under
    `gt.attn.kda_mixer` by its name and none under `gt.attn.kda_rule`; no view
    of an activation by (tokens, 32, 128) is left ANYWHERE in the module; and
    under the mixer's scope no float32 (tokens, 4096) or (tokens, 12288) array
    is reshaped, copied, transposed or broadcast and no slice, pad or
    concatenation of an activation is written out."""
    from galvatron_tpu.obs import tracing

    text, took = kimi_kda_layer
    assert took == {part: {"pallas": 1} for part in (forms.KDA_RULE, forms.KDA_CONV_NORM, forms.KDA_GATE, forms.KDA_GATED_NORM)}
    tokens, smallest = 8192, 2048
    passes = {"conv_norm_fwd": 3, "conv_norm_bwd": 3, "kda_gate_fwd": 1, "kda_gate_bwd": 1,
              "gated_norm_fwd": 1, "gated_norm_bwd": 1}  # a call each for q, k and v; forward and recomputation are one here
    for kernel, count in passes.items():
        assert _calls(text, kernel, tracing.ATTN_KDA) == count, kernel
        assert not _calls(text, kernel, tracing.ATTN_KDA_RULE), kernel
    assert text.count("tpu_custom_call") == 2 + sum(passes.values())
    assert not re.search(r"\[(?:1,)?%d,32,128\]" % tokens, text)  # no view by heads
    offenders = []
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or tracing.ATTN_KDA not in line:
            continue
        name, result, kind = found.groups()
        over_tokens = {dtype for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]*)\]", result)
                       if str(tokens) in dims.split(",")
                       and np.prod([int(d) for d in dims.split(",")]) >= tokens * smallest}
        if (("f32" in over_tokens and kind in ("reshape", "copy", "transpose", "broadcast"))
                or (over_tokens and kind in ("slice", "dynamic-slice", "pad", "concatenate"))):
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)

"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the window kernels at the Laguna cell's shapes, on a dp4 mesh, and a whole window layer as projected."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import _calls, v5e_2x2  # noqa: F401  (the fixture)


# ------------------------------------------------- the window kernels (Laguna)
FLASH_PATTERNS = (r"^flash_attention[.:]", r"^flash_mha_bwd_dkv", r"^flash_mha_bwd_dq")  # benchmarks/layer_metrics/flash_ms.py


def _window_loss(sharding):
    def loss(q, k, v):
        with jax.named_scope("gt.layers.r1"):  # as in the step: the kernels' calls lie inside a run's scope
            out = A.core_attention(q, k, v, window=512, sharding=sharding)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


def _custom_calls(text):
    """The names of a compiled program's Mosaic calls, as the trace labels them."""
    return sorted(line.split("=")[0].strip().lstrip("%") for line in text.splitlines()
                  if "custom_call_target=\"tpu_custom_call\"" in line)


@pytest.mark.parametrize("tokens", [8192, 16384])
def test_the_window_kernels_compile_at_the_cells_shapes_for_v5e(v5e_2x2, tokens):
    """64 query heads on 8 KV heads of 128 under a window of 512, the Laguna
    cell's window layers (and at twice their tokens, scripts/laguna_chip_check.py's
    16384), through `impl="auto"`: two Mosaic calls, forward and backward, whose
    names NONE of `flash_ms`'s three patterns match (or `flash_roofline` would
    price a band as a causal triangle), each on ONE line of the compiled text
    with its `op_name` (the benchmark's trace reader reads an instruction's
    first line: a kernel with `metadata=`, as jax's splash kernels, loses its
    scope there), k and v at their own 8 heads, and under a tenth of the
    temporaries a repeat of k and v to 64 heads would take."""
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, tokens, 64, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, tokens, 8, 128), jnp.bfloat16, sharding=one)
    fn = jax.grad(_window_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))), argnums=(0, 1, 2))
    with forms.recording() as took:
        compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    names = _custom_calls(text)
    assert [n.rsplit(".", 1)[0] for n in names] == ["window_attn_bwd", "window_attn_fwd"]
    assert not any(re.search(rx, name) for rx in FLASH_PATTERNS for name in names)
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" in line:
            assert "gt.layers.r1" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert took == {forms.WINDOW_ATTENTION: {"pallas": 1}}
    # q, its cotangent's float32 square, the transposes and the backward's float32 shares of dk and dv
    # (4 x 32 MiB at 8192): no 64-head copy of k or v (2 x 128 MiB at 8192)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30 * tokens / 8192


@pytest.mark.parametrize("as_projected", [False, True], ids=["q_turned_before", "as_projected"])
def test_the_window_kernels_run_in_a_manual_region_on_a_dp4_mesh(v5e_2x2, as_projected):
    """Four sequences over four chips (dp with ZeRO runs the family): each chip
    its own row through the kernels, its rows of the rotation's tables and of
    the gate logits with it where the call brings them, no collective."""
    from galvatron_tpu.ops.rope import half_split_tables

    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "m0"))
    sh = NamedSharding(mesh, P("m0", None, None, None))
    q = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 2048, 4, 128), jnp.bfloat16, sharding=sh)
    logits = jax.ShapeDtypeStruct((4, 2048, 16), jnp.bfloat16, sharding=NamedSharding(mesh, P("m0", None, None)))
    where = A.KernelSharding(mesh, ("m0",), ())

    def loss(q, k, v, logits):
        if not as_projected:
            return _window_loss(where)(q, k, v)
        positions = jnp.broadcast_to(jnp.arange(2048), (4, 2048))
        out = A.core_attention(q, k, v, window=512, sharding=where, q_rope=half_split_tables(positions, 128),
                               head_gate=logits)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(q, kv, kv, logits).compile().as_text()
    assert len(_custom_calls(text)) == 2
    for collective in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert collective not in text, collective


@pytest.fixture(scope="module")
def laguna_window_layer(v5e_2x2):
    """One window layer's mixer of the Laguna cell (8192 tokens, hidden 2048,
    64 query heads on 8 KV heads of 128, a window of 512, the gate a head,
    bf16) under the cell's recomputation, forward and backward, compiled for one
    described chip: -> (the optimised module's text, the forms its call took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.laguna import laguna_config
    from galvatron_tpu.models.parts.window import window_mixer

    tokens = 8192
    cfg = laguna_config(num_layers=5, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("window")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({name: shapes[name] for name in ("wq", "wkv", "wo", "wg")},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16),
                             jax.ShapeDtypeStruct((1, tokens), jnp.int32)))

    def loss(p, y, positions):
        mixer = jax.checkpoint(lambda p, y: window_mixer(p, y, positions, lcfg, mesh=None, axes=None, attn_bias=None,
                                                         attn_sharding=where, return_kv=False))
        return jnp.sum(mixer(p, y)[0].astype(jnp.float32))

    with forms.recording() as took:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, took


def test_the_window_layer_reads_q_where_the_projection_wrote_it_on_v5e(laguna_window_layer):
    """A window layer on a TPU (PR 50): Mosaic compiles both kernels in the
    as-projected form, `window_attn_fwd` and `window_attn_bwd` once each under
    `gt.attn.band` (rope's tables and the gate logits among their operands), and
    nothing else of the layer makes a pass over a q-sized array: NO array by
    heads ((8192, 64, 128) or (64, 8192, 128), any dtype) is left anywhere in
    the module, and every (8192, 64 x 128) result of an instruction is a
    kernel's or a matmul's own (a fusion around a convolution): no transpose,
    no copy, no elementwise pass between the q projection and the kernel, the
    kernel and `wo`, `wo`'s backward and the kernel, the kernel and the
    projection's backward."""
    from galvatron_tpu.obs import tracing

    text, took = laguna_window_layer
    assert took == {forms.WINDOW_ATTENTION: {"pallas": 1}, forms.WINDOW_OPERANDS: {"as_projected": 1}}
    assert _calls(text, "window_attn_fwd", tracing.ATTN_WINDOW_BAND) == 1
    assert _calls(text, "window_attn_bwd", tracing.ATTN_WINDOW_BAND) == 1
    assert text.count("tpu_custom_call") == 2
    tokens, width = 8192, 64 * 128
    assert not re.search(r"\[(?:1,)?(?:%d,64|64,%d),128\]" % (tokens, tokens), text)  # no view by heads
    entry = text[text.index("\nENTRY"):]
    q_sized = r"(?:bf16|f32)\[(?:1,)?%d,%d\]" % (tokens, width)
    offenders, matmuls = [], 0
    for line in entry.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or not re.search(q_sized, found.group(2)):
            continue
        name, result, kind = found.groups()
        if kind in ("get-tuple-element", "bitcast", "parameter") or "tpu_custom_call" in line:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        body = text[text.index("\n" + called.group(1) + " "):].split("\n}\n", 1)[0] if called else ""
        if kind == "fusion" and " convolution(" in body:
            matmuls += 1
        else:
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)
    assert matmuls == 2  # the q projection (recomputed: the first forward is the same program here) and wo's backward
    # the kernels take the flat projection's result and wo's cotangent as they lie, and dq goes to the matmuls so
    for kernel, operand in (("window_attn_fwd", "convolution"), ("window_attn_bwd", "convolution")):
        call = next(line for line in entry.splitlines() if "tpu_custom_call" in line and kernel in line.split("=")[0])
        assert re.search(r"custom-call\(%" + operand, call), call[:200]

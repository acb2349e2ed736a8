"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the routed-experts block at OLMoE's widths, one chip and dp4, the row movers at their bounds and at a width that is
no whole tiles of words (Kimi-Linear's 2304, PR 63), and that width's whole block."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import MEGABLOX_CALL, _calls, v5e_2x2  # noqa: F401  (the fixture)


# ------------------------------------------------------------ routed experts
MOE_TOKENS, MOE_H, MOE_F, MOE_E, MOE_K = 8192, 2048, 1024, 64, 8  # OLMoE-1B-7B, 2 x 4096


def _moe_loss(sharding):
    from galvatron_tpu.ops.moe import moe_ffn

    def loss(y, router, wi, wo):
        out, aux = moe_ffn(y, router, wi, wo, experts_per_token=MOE_K, dtype=y.dtype,
                           sharding=sharding)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux["load_balance"] + aux["router_z"]

    return loss


def _moe_operands(batch, tokens_sharding, whole, dtype=jnp.bfloat16):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((batch, MOE_TOKENS // 2, MOE_H), dtype, sharding=tokens_sharding),
            jax.ShapeDtypeStruct((MOE_H, MOE_E), f32, sharding=whole),
            jax.ShapeDtypeStruct((MOE_E, MOE_H, 2 * MOE_F), f32, sharding=whole),
            jax.ShapeDtypeStruct((MOE_E, MOE_F, MOE_H), f32, sharding=whole))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_the_routed_experts_block_compiles_for_v5e(v5e_2x2, dtype):
    """ops/moe.py at OLMoE's widths, forward and backward, one chip: on a TPU
    (read off the mesh, as the flash kernel's dispatch) the grouped matmuls are
    the megablox kernels at the measured tiling, which the chip's compiler
    takes (float32 operands at half the K and N tiles: the whole ones exceed
    the scoped VMEM); off it, `ragged_dot`."""
    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))
    fn = jax.grad(_moe_loss(on_chip), argnums=(0, 1, 2, 3))
    text = jax.jit(fn).lower(*_moe_operands(2, one, one, dtype)).compile().as_text()
    assert len(re.findall(MEGABLOX_CALL, text)) == 6  # 2 forward, 4 backward
    assert "ragged-dot" not in text
    if dtype == jnp.float32:
        return
    off_chip = jax.jit(_moe_loss(None)).lower(*_moe_operands(2, one, one)).compile().as_text()
    assert "ragged-dot" in off_chip and not re.findall(MEGABLOX_CALL, off_chip)


def test_the_routed_experts_block_is_a_manual_region_on_a_dp4_mesh(v5e_2x2):
    """Under dp the block runs per device on its own batch rows against whole
    experts (a region manual over every axis, as the flash kernel's), and the
    only collectives are the sums of the router's statistics and of the
    parameters' gradients."""
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "dp"))
    sharding = A.KernelSharding(mesh, batch_axes=("dp",))
    fn = jax.grad(_moe_loss(sharding), argnums=(0, 1, 2, 3))
    text = jax.jit(fn).lower(*_moe_operands(
        8, NamedSharding(mesh, P("dp", None, None)), NamedSharding(mesh, P()))).compile().as_text()
    assert len(re.findall(MEGABLOX_CALL, text)) == 6
    assert "all-reduce" in text and "all-to-all" not in text and "all-gather" not in text


def _mover_calls(k, tokens, hidden, one):
    """The three row movers alone, jitted, and their operands at a block of
    `tokens` x `k` assignments of `hidden` bf16."""
    from galvatron_tpu.ops import moe

    rows, words = k * tokens, hidden // 256
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    back = lambda packed, inv, w=None: moe._rows_back(packed, inv, w, tokens, hidden, jnp.bfloat16, moe.ROWS_BACK_TILE)
    return {
        "moe_rows_pack": (lambda x: moe._pack_rows(x, moe.PACK_TILE), shaped((rows, hidden), jnp.bfloat16)),
        "moe_rows_back": (back, shaped((rows * words, 128), jnp.uint32), shaped((rows,), jnp.int32),
                          shaped((tokens, k), jnp.float32)),
        "moe_rows_back, no weights": (back, shaped((rows * words, 128), jnp.uint32), shaped((rows,), jnp.int32)),
        "moe_rows_out": (lambda *operands: moe._rows_out(*operands, moe.ROWS_OUT_TILE), shaped((tokens * words, 128), jnp.uint32), shaped((rows,), jnp.int32),
                         shaped((rows, hidden), jnp.bfloat16), shaped((rows,), jnp.float32)),
    }


def test_the_row_movers_compile_at_the_largest_block_they_take_on_v5e(v5e_2x2):
    """`ops/moe.rows_form` has upper bounds, and they are what Mosaic was
    seen to take: every assignment's index is prefetched into SMEM (1 MiB on
    a v5e), so at `ROWS_MAX_ASSIGNMENTS` x `ROWS_MAX_HIDDEN` the three kernels
    compile, and a block a third longer (32768 tokens x 8: all of SMEM) is
    refused BY THE COMPILER, which is why `rows_form` hands it to XLA, as the
    parent did, before it gets there."""
    from galvatron_tpu.ops import moe

    one, bf16 = SingleDeviceSharding(v5e_2x2[0]), jnp.bfloat16
    k, hidden = 8, moe.ROWS_MAX_HIDDEN
    tokens = moe.ROWS_MAX_ASSIGNMENTS // k
    assert moe.rows_form(True, bf16, hidden, tokens, k) == "kernel"
    for name, (fn, *operands) in _mover_calls(k, tokens, hidden, one).items():
        assert "tpu_custom_call" in jax.jit(fn).lower(*operands).compile().as_text(), name
    longer = 32768
    assert moe.rows_form(True, bf16, hidden, longer, k) == "xla"
    assert moe.rows_form(True, bf16, 2 * hidden, tokens, k) == "xla"
    fn, *operands = _mover_calls(k, longer, hidden, one)["moe_rows_back"]
    with pytest.raises(Exception, match="smem"):
        jax.jit(fn).lower(*operands).compile()


# ------------------------------- a row that is no whole tiles of words (PR 63)
KIMI_H, KIMI_E, KIMI_HELD, KIMI_F = 2304, 256, (0, 8), 1024  # kimilin-c1-s8k's routed block: 8192 tokens, k = 8


def test_packed_rows_lie_end_to_end_at_every_width():
    """A packed row is `hidden / 256` sublane rows of 128 words and the next
    row follows it at once: no pitch, no padding, so at 2048 and 4096 (whole
    tiles a row) the kernels are the ones PR 40 compiled, and at Kimi-Linear's
    2304 a row is 9 sublane rows that straddle a tile's edge."""
    from galvatron_tpu.ops import moe

    for hidden, words in ((2048, 8), (2304, 9), (3584, 14), (4096, 16)):
        rows = jax.ShapeDtypeStruct((1024, hidden), jnp.bfloat16)
        assert jax.eval_shape(lambda x: moe._pack_rows(x, moe.PACK_TILE), rows).shape == (1024 * words, 128)


def test_the_row_movers_compile_at_a_row_that_is_no_whole_tiles_on_v5e(v5e_2x2):
    """Kimi-Linear's block (8192 tokens x 8 assignments of 2304 bf16, a row of
    9 sublane rows of words) and every other width `rows_form` lets through:
    the three kernels compile for the chip, `moe_rows_back` with and without
    the router's weights (the combine's forward, the dispatch's backward).
    Mosaic takes the slices of a row at starts that are no multiple of 8."""
    from galvatron_tpu.ops import moe

    one = SingleDeviceSharding(v5e_2x2[0])
    for hidden in range(moe.ROWS_MIN_HIDDEN, moe.ROWS_MAX_HIDDEN + 1, 256):
        assert moe.rows_form(True, jnp.bfloat16, hidden, MOE_TOKENS, MOE_K) == "kernel"
        for name, (fn, *operands) in _mover_calls(MOE_K, MOE_TOKENS, hidden, one).items():
            assert "tpu_custom_call" in jax.jit(fn).lower(*operands).compile().as_text(), (name, hidden)


def test_a_routed_block_of_that_width_moves_its_rows_by_the_kernels_on_v5e(v5e_2x2):
    """The whole block as `kimilin-c1-s8k` runs it (a sigmoid router with a
    bias over 256 experts, 8 of them held, so the experts work on a window
    inside a `cond`), forward and backward: the form says "kernel", the
    combine's forward and both backwards are the movers' calls under their
    scopes, and no XLA gather of the (65536, 2304) rows is left under the
    combine (the dispatch's own forward stays XLA's gather, as at 2048)."""
    from galvatron_tpu.obs import forms, tracing
    from galvatron_tpu.ops.moe import moe_ffn

    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))

    def loss(y, router, wi, wo, bias):
        out, _ = moe_ffn(y, router, wi, wo, experts_per_token=MOE_K, dtype=y.dtype, sharding=on_chip,
                         score="sigmoid", norm_topk_prob=True, scale=2.446, bias=bias, held=KIMI_HELD)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    shaped = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    operands = (shaped((1, MOE_TOKENS, KIMI_H), jnp.bfloat16), shaped((KIMI_H, KIMI_E)),
                shaped((KIMI_HELD[1], KIMI_H, 2 * KIMI_F)), shaped((KIMI_HELD[1], KIMI_F, KIMI_H)), shaped((KIMI_E,)))
    with forms.recording() as took:
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(*operands).compile().as_text()
    assert took[forms.MOE_ROWS] == {"kernel": 1}
    rows = "%d,%d" % (MOE_K * MOE_TOKENS, KIMI_H)
    gathers = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.split("\n")
               if " gather(" in line and "[%s]" % rows in line.split(" gather(")[0]]
    assert gathers and all(tracing.MOE_DISPATCH in g for g in gathers), gathers
    assert _calls(text, "moe_rows_back", tracing.MOE_COMBINE) and _calls(text, "moe_rows_out", tracing.MOE_COMBINE)
    assert _calls(text, "moe_rows_back", tracing.MOE_DISPATCH)

"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the routed-experts block at OLMoE's widths, one chip and dp4, and the row movers at their bounds."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import MEGABLOX_CALL, v5e_2x2  # noqa: F401  (the fixture)


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
    return {
        "moe_rows_pack": (lambda x: moe._pack_rows(x, moe.PACK_TILE), shaped((rows, hidden), jnp.bfloat16)),
        "moe_rows_back": (lambda packed, inv, w: moe._rows_back(packed, inv, w, tokens, hidden, jnp.bfloat16,
                                                                 moe.ROWS_BACK_TILE),
                          shaped((rows * words, 128), jnp.uint32), shaped((rows,), jnp.int32),
                          shaped((tokens, k), jnp.float32)),
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

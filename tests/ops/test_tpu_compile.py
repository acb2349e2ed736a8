"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the flash kernels at the cells' head widths, alone and on a mesh, and `chip_smoke.py`'s refusal."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import B, HD, NH, REPO, S, _attn_loss, _qkv, v5e_2x2  # noqa: F401  (the fixture)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles_for_v5e(v5e_2x2, backward):
    """The repo's block sizes (1024 x 512) at 7B attention shapes, causal."""
    one = SingleDeviceSharding(v5e_2x2[0])
    fn = _attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",))))
    if backward:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(one), _qkv(one), _qkv(one)).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_kernel_compiles_at_granites_64_wide_heads_for_v5e(v5e_2x2):
    """32 query heads on 8 KV heads of 64 at 4096 tokens, forward and the two
    backward kernels, through `impl="auto"`: Mosaic takes the 64-wide heads as
    they are, so the dispatch pads nothing and never falls to XLA's (b, nh, s,
    s) float32 logits (2 GiB at these shapes)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    shd = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))

    def loss(q, k, v):
        out = A.core_attention(q, k, v, causal=True, sm_scale=0.015625, sharding=shd)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16, sharding=one)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3 and "f32[1,32,4096,4096]" not in text


def test_flash_segment_id_form_compiles_for_v5e(v5e_2x2):
    """A key-padding bias rides the kernel as segment ids (forward+backward)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one)
    fn = jax.grad(_attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))),
                  argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(one), _qkv(one), _qkv(one), bias).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_flash_kernel_compiles_on_2x2_mesh(v5e_2x2):
    """Batch over 2, heads over 2: under GSPMD alone this raises 'Mosaic
    kernels cannot be automatically partitioned'; the manual region
    (ops/attention.KernelSharding) gives each chip its own rows and heads,
    and needs no collective to do so."""
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 2, 2), ("pp", "m0", "m1"))
    sh = NamedSharding(mesh, P("m0", None, "m1", None))
    fn = jax.grad(_attn_loss(A.KernelSharding(mesh, ("m0",), ("m1",))), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(sh), _qkv(sh), _qkv(sh)).compile().as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert collective not in text, collective


def test_auto_dispatch_reads_the_platform_off_the_mesh(v5e_2x2):
    """impl='auto' on a described-TPU mesh takes the kernel although this
    process's default backend is the CPU — the branch the chip takes."""
    assert jax.default_backend() == "cpu"
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "m0"))
    sh = NamedSharding(mesh, P("m0", None, None, None))
    shd = A.KernelSharding(mesh, ("m0",), ())

    def fwd(q, k, v):
        return A.core_attention(q, k, v, causal=True, sharding=shd)

    q = jax.ShapeDtypeStruct((4, S, NH, HD), jnp.bfloat16, sharding=sh)
    assert "tpu_custom_call" in jax.jit(fwd).lower(q, q, q).compile().as_text()


def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py on the CPU exits non-zero before any work and prints no
    verdict — a measurement path that finds no chip fails, it does not fall
    back."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip() == "", proc.stdout


# ------------------------- GLM-4.7-Flash: the kernels' shapes new with PR 32
def test_flash_kernel_compiles_at_head_dim_256_for_v5e(v5e_2x2):
    """Latent attention calls the kernel once at q/k = v = 256 dims a head (20
    heads, 8192 tokens): the repo's 1024 x 512 blocks, which every other cell
    runs at 128, still fit the scoped VMEM at twice the width in bf16 (in
    float32 they do not: a float32 program of this family takes XLA's path)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    operand = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=one)
    fn = jax.grad(_attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))),
                  argnums=(0, 1, 2))
    text = jax.jit(fn).lower(operand, operand, operand).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dkv, dq
    assert "block_q_1024" in text and "block_k_512" in text.replace("block_k_major_512", "block_k_512")

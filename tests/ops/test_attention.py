"""Attention op correctness: ring attention vs dense reference, zigzag layout,
GQA, rope."""

import unittest.mock as mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.ops.attention import core_attention, repeat_kv
from galvatron_tpu.ops.rope import apply_rotary
from tests.ops.attention_operands import _rand_qkv

pytestmark = [pytest.mark.parallel]


def test_xla_attention_causal_matches_manual():
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    out = core_attention(q, k, v, causal=True, impl="xla")
    s = q.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = np.tril(np.ones((s, s), bool))
    logits = jnp.where(mask[None, None], logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gqa_repeat():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), nh=8, nkv=2)
    out = core_attention(q, k, v, causal=True, impl="xla")
    out2 = core_attention(q, repeat_kv(k, 4), repeat_kv(v, 4), causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


def test_rope_rotation_invariants():
    b, s, nh, hd = 1, 8, 2, 16
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, nh, hd))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    out = apply_rotary(x, pos)
    # norms preserved
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1), np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]), atol=1e-6)
    # relative property: shifting positions rotates q,k equally -> same scores
    q = jax.random.normal(jax.random.PRNGKey(4), (b, s, nh, hd))
    s1 = jnp.einsum("bqhd,bkhd->bhqk", apply_rotary(q, pos), apply_rotary(x, pos))
    s2 = jnp.einsum("bqhd,bkhd->bhqk", apply_rotary(q, pos + 7), apply_rotary(x, pos + 7))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-3)


def test_flash_block_sizes_divide_sequence():
    """Every seq the auto-dispatch can route to flash (multiples of 128) must
    get block sizes that divide it (review finding: 768 crashed the kernel)."""
    from galvatron_tpu.ops.attention import _flash_divisor

    for s in (128, 256, 384, 512, 640, 768, 896, 1024, 1152, 1536, 2048, 4096):
        for cap in (512, 1024):
            b = _flash_divisor(s, cap)
            assert s % b == 0 and b <= cap, (s, cap, b)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_match_xla_padding(causal):
    """Padded-mask flash (VERDICT r4 item 3): the key-padding bias lowers to
    segment ids on the flash path instead of the O(S^2) XLA fallback; kernel
    run in pallas interpret mode, compared to _xla_attention with the
    additive bias on the valid query rows (padded rows are garbage under
    both schemes and masked downstream)."""
    import jax.experimental.pallas.tpu as pltpu

    if not hasattr(pltpu, "force_tpu_interpret_mode"):
        pytest.skip("pallas interpret-mode context manager not in this jax "
                    "(0.4.x); kernel-vs-XLA parity needs it on a CPU host")

    from galvatron_tpu.ops.attention import (
        _pallas_flash,
        _xla_attention,
        padding_bias_to_segment_ids,
    )

    b, s, nh, hd = 2, 256, 2, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(31), b=b, s=s, nh=nh, hd=hd)
    mask = np.ones((b, s), np.float32)
    mask[0, -64:] = 0.0
    mask[1, -128:] = 0.0
    bias = jnp.asarray((1.0 - mask)[:, None, None, :] * -1e9)
    seg = padding_bias_to_segment_ids(bias)
    np.testing.assert_array_equal(np.asarray(seg.kv), mask.astype(np.int32))
    with pltpu.force_tpu_interpret_mode():
        out_f = _pallas_flash(q, k, v, causal=causal, sm_scale=hd**-0.5,
                              segment_ids=seg)
    out_x = _xla_attention(q, k, v, causal=causal, sm_scale=hd**-0.5, bias=bias)
    valid = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(out_f)[valid], np.asarray(out_x)[valid],
                               atol=3e-5)


def test_sharded_flash_kernel_matches_xla(devices8):
    """The kernel under sharding (KernelSharding: one manual region, batch
    over m0, heads over m1) against XLA attention on the same sharded
    operands — pallas interpret mode on a 2x2 CPU mesh (forward; the
    backward under sharding is compiled for the chip in test_tpu_compile.py
    and run by chip_smoke.py). What GSPMD cannot partition on the chip is
    computed per device here."""
    import jax.experimental.pallas.tpu as pltpu

    from galvatron_tpu.ops import attention as A

    mesh = Mesh(np.array(devices8[:4]).reshape(1, 2, 2), ("pp", "m0", "m1"))
    b, s, nh, hd = 2, 256, 2, 128
    sh = NamedSharding(mesh, P("m0", None, "m1", None))
    q, k, v = (jax.device_put(t, sh)
               for t in _rand_qkv(jax.random.PRNGKey(34), b=b, s=s, nh=nh, hd=hd))
    shd = A.KernelSharding(mesh, ("m0",), ("m1",))

    def flash(q_):
        return A.core_attention(q_, k, v, causal=True, impl="flash", sharding=shd)

    def xla(q_):
        return A._xla_attention(q_, k, v, causal=True, sm_scale=hd**-0.5)

    with pltpu.force_tpu_interpret_mode():
        out = jax.jit(flash)(q)
    assert out.sharding.is_equivalent_to(sh, out.ndim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(xla(q)), atol=3e-5)


def test_core_attention_padding_dispatch_stays_flash_eligible():
    """Dispatch logic: a key-padding bias keeps flash eligibility (lowered to
    segment ids) while a generic additive bias (T5 relative positions) and
    cross-shaped biases still fall back to XLA."""
    from galvatron_tpu.ops import attention as A

    b, s, nh, hd = 2, 256, 2, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(32), b=b, s=s, nh=nh, hd=hd)
    mask = np.ones((b, s), np.float32)
    mask[:, -64:] = 0.0
    pad_bias = jnp.asarray((1.0 - mask)[:, None, None, :] * -1e9)

    calls = []
    orig = A._pallas_flash

    def spy(q_, k_, v_, **kw):
        calls.append(kw.get("segment_ids") is not None)
        import jax.experimental.pallas.tpu as pltpu

        if hasattr(pltpu, "force_tpu_interpret_mode"):
            with pltpu.force_tpu_interpret_mode():
                return orig(q_, k_, v_, **kw)
        # jax <= 0.4.37 has no TPU interpret mode: emulate the kernel's
        # segment-id semantics on the XLA path (only VALID rows are asserted
        # below, where the two schemes agree by construction)
        seg = kw.get("segment_ids")
        emu_bias = jnp.where(seg.kv[:, None, None, :] > 0, 0.0, -1e9)
        return A._xla_attention(q_, k_, v_, causal=kw.get("causal", False),
                                sm_scale=kw["sm_scale"], bias=emu_bias)

    import unittest.mock as mock

    with mock.patch.object(A, "_pallas_flash", spy), \
         mock.patch.object(jax, "default_backend", lambda: "tpu"):
        out = A.core_attention(q, k, v, causal=False, bias=pad_bias,
                               bias_type="key_padding")
        # generic additive bias: must NOT hit the kernel
        rel = jnp.zeros((1, nh, s, s), jnp.float32)
        A.core_attention(q, k, v, causal=False, bias=rel)
    assert calls == [True], calls
    ref = A._xla_attention(q, k, v, causal=False, sm_scale=hd**-0.5, bias=pad_bias)
    valid = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(out)[valid], np.asarray(ref)[valid],
                               atol=3e-5)


def test_explicit_flash_with_untileable_padded_batch_falls_back():
    """impl="flash" families (gpt_fa/llama_fa) with a padded batch at a seq
    the kernel cannot tile (not a multiple of 128) must keep the XLA
    fallback, not crash in the kernel."""
    from galvatron_tpu.ops import attention as A

    b, s, nh, hd = 2, 96, 2, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(33), b=b, s=s, nh=nh, hd=hd)
    mask = np.ones((b, s), np.float32)
    mask[:, -16:] = 0.0
    bias = jnp.asarray((1.0 - mask)[:, None, None, :] * -1e9)
    out = A.core_attention(q, k, v, causal=False, bias=bias, impl="flash",
                           bias_type="key_padding")
    ref = A._xla_attention(q, k, v, causal=False, sm_scale=hd**-0.5, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_explicit_flash_key_padding_on_cpu_falls_back():
    """ADVICE r5: impl="flash" with a key-padding bias at kernel-tileable
    shapes must still fall back to XLA off-TPU (jax.default_backend() is
    "cpu" here) instead of dispatching the pallas segment-id kernel."""
    from galvatron_tpu.ops import attention as A

    b, s, nh, hd = 2, 256, 2, 128
    q, k, v = _rand_qkv(jax.random.PRNGKey(40), b=b, s=s, nh=nh, hd=hd)
    mask = np.ones((b, s), np.float32)
    mask[:, -64:] = 0.0
    bias = jnp.asarray((1.0 - mask)[:, None, None, :] * -1e9)
    assert jax.default_backend() == "cpu"
    out = A.core_attention(q, k, v, causal=False, bias=bias, impl="flash",
                           bias_type="key_padding")
    ref = A._xla_attention(q, k, v, causal=False, sm_scale=hd**-0.5, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_key_padding_cross_attention_lengths_fail_loudly():
    """ADVICE r5: bias_type="key_padding" is a self-attention contract (the
    segment-id lowering reuses the key mask for queries); a cross-attention
    call with q_len != kv_len must raise instead of returning silently wrong
    valid-row outputs."""
    import pytest

    from galvatron_tpu.ops import attention as A

    q, _, _ = _rand_qkv(jax.random.PRNGKey(41), s=64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(42), s=32)
    bias = jnp.zeros((2, 1, 1, 32), jnp.float32)
    with pytest.raises(ValueError, match="SELF-attention"):
        A.core_attention(q, k, v, causal=False, bias=bias,
                         bias_type="key_padding")


# ------------------------------------------------- 64-wide heads (Granite-4.0-H)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_the_kernel_at_head_dim_64_is_the_xla_form(grad):
    """GQA 4 on 2 heads of 64 at Granite's scale (NOT 1 / sqrt(64)): the flash
    kernels take the 64-wide heads as they are (interpret mode), forward and
    the three gradients against `_xla_attention` in float32."""
    import jax.experimental.pallas.tpu as pltpu

    from galvatron_tpu.ops import attention as A

    q, k, v = _rand_qkv(jax.random.PRNGKey(64), b=1, s=256, nh=4, nkv=2, hd=64)
    scale = 0.3  # neither Granite's 0.015625 (a nearly uniform softmax) nor 1 / sqrt(64) = 0.125

    def loss(impl):
        def f(q, k, v):
            out = A.core_attention(q, k, v, causal=True, sm_scale=scale, impl=impl)
            return jnp.sum(jnp.sin(out)) if grad else out
        return jax.grad(f, argnums=(0, 1, 2)) if grad else f

    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = loss("flash")(q, k, v)
        want = loss("xla")(q, k, v)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5)
    # and the scale is the one passed, not one derived from the head's width
    other = A.core_attention(q, k, v, causal=True, impl="xla")
    assert float(jnp.max(jnp.abs(other - (want[0] if grad else want)))) > 1e-3 or grad


def test_auto_dispatch_takes_the_kernel_at_head_dim_64_on_a_tpu_and_says_a_fallback_once(caplog):
    """On a TPU `impl="auto"` no longer sends 64-wide heads to XLA's float32
    (b, nh, s, s) logits; what still falls back at a tileable length (heads of
    32) is logged, once a shape, with what it costs."""
    import logging
    import unittest.mock as mock

    from galvatron_tpu.ops import attention as A

    calls = []

    def spy(q_, k_, v_, **kw):
        calls.append((q_.shape[-1], kw["sm_scale"]))
        return A._xla_attention(q_, k_, v_, causal=kw["causal"], sm_scale=kw["sm_scale"])

    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b=1, s=256, nh=4, nkv=2, hd=64)
    narrow = _rand_qkv(jax.random.PRNGKey(6), b=1, s=256, nh=4, hd=32)
    A._FALLBACKS_SAID.clear()
    with mock.patch.object(A, "_pallas_flash", spy), \
         mock.patch.object(jax, "default_backend", lambda: "tpu"), \
         caplog.at_level(logging.WARNING, logger=A.__name__):
        out = A.core_attention(q, k, v, causal=True, sm_scale=0.015625)
        for _ in range(2):
            A.core_attention(*narrow, causal=True)
    assert calls == [(64, 0.015625)]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(A.core_attention(q, k, v, causal=True, sm_scale=0.015625, impl="xla")),
        atol=2e-5)
    said = [r.getMessage() for r in caplog.records if "XLA attention on a TPU" in r.getMessage()]
    assert len(said) == 1 and "head_dim 32" in said[0] and "(1, 4, 256, 256)" in said[0]
    # off a TPU nothing is said: the CPU's tests and the serve path fall back by design
    caplog.clear()
    A.core_attention(*narrow, causal=True)
    assert not caplog.records

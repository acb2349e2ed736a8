"""Attention over a window (`core_attention(window=)`): the band against the per-token loop, the window kernels
(ops/window_attention.py, interpreted) against the band mask, the dispatch, and what a window layer hands the kernels."""

import unittest.mock as mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.obs import forms
from galvatron_tpu.ops.rope import apply_rotary
from tests.ops.attention_operands import _rand_qkv

pytestmark = [pytest.mark.parallel]


# ------------------------------------------------------- a window of keys (Laguna)
def _per_token_window(q, k, v, window, scale):
    """Query i on the keys i - window < j <= i, a loop a batch row, head and token, float64."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    b, s, nh, hd = q.shape
    group = nh // k.shape[2]
    out = np.zeros_like(q)
    for row in range(b):
        for h in range(nh):
            for i in range(s):
                first = max(0, i - window + 1)
                scores = k[row, first:i + 1, h // group] @ q[row, i, h] * scale
                p = np.exp(scores - scores.max())
                out[row, i, h] = (p / p.sum()) @ v[row, first:i + 1, h // group]
    return out


@pytest.mark.parametrize("window", [1, 5, 16, 40, 41, 64])
def test_the_band_is_the_per_token_loop_and_a_window_of_the_whole_sequence_is_causal(window):
    """GQA 4 on 2 over 40 tokens: the band mask against a loop a token (a
    window of 1 is the token's own value), and at 40 keys and more plain causal
    attention, bit for bit the same logits."""
    from galvatron_tpu.ops import attention as A

    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=2, s=40, nh=4, nkv=2, hd=16)
    with jax.default_matmul_precision("highest"):
        got = A.core_attention(q, k, v, window=window, sm_scale=0.4)
        causal = A.core_attention(q, k, v, causal=True, sm_scale=0.4, impl="xla")
    np.testing.assert_allclose(np.asarray(got), _per_token_window(q, k, v, window, 0.4), atol=2e-5)
    if window == 1:
        np.testing.assert_allclose(np.asarray(got), np.asarray(A.repeat_kv(v, 2)), atol=1e-6)
    if window >= 40:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(causal))
    else:
        assert float(jnp.max(jnp.abs(got - causal))) > 1e-3


def test_a_window_is_causal_self_attentions_and_counts_its_form():
    from galvatron_tpu.ops import attention as A

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, s=32, nh=2, hd=16)
    with pytest.raises(ValueError, match="a window of 4 keys is causal self-attention's"):
        A.core_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="32 queries on 16 keys"):
        A.core_attention(q, k[:, :16], v[:, :16], window=4)
    with pytest.raises(ValueError, match="a window of 0 keys"):
        A.core_attention(q, k, v, window=0)
    with forms.recording() as took:
        A.core_attention(q, k, v, window=4)
        A.core_attention(q, k, v, window=4, impl="flash")  # off a TPU the kernels have no form: the band mask
        assert took == {forms.WINDOW_ATTENTION: {"xla": 2}}
        A.core_attention(q, k, v, causal=True)  # no window: not counted
    assert took == {forms.WINDOW_ATTENTION: {"xla": 2}}


@pytest.mark.parametrize("window,block", [(160, 128), (128, 128), (129, 128), (300, 128), (64, 256), (1, 128), (600, 256)])
def test_the_window_kernels_are_the_band_mask(window, block):
    """GQA 4 on 2 heads of 128 at 512 tokens: the repo's band kernels
    (`ops/window_attention.py`, interpret mode), forward and the three
    gradients against the band mask on XLA's logits in float32, at windows
    that end on a block's edge (128), one past it (129), inside a block, over
    three blocks before the query's own (300 at 128) and wider than the
    sequence (600: plain causal attention)."""
    import jax.experimental.pallas.tpu as pltpu

    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import window_attention as W

    q, k, v = _rand_qkv(jax.random.PRNGKey(9), b=2, s=512, nh=4, nkv=2, hd=128)
    scale = 0.05
    assert W.block_for(512, window, block) == block

    def grads(kernel):
        def f(q, k, v):
            if kernel:  # as projected: a head a block of 128 lanes of a (batch, seq, heads x 128) array
                out = W.window_attention(*(t.reshape(2, 512, -1) for t in (q, k, v)), None, None, window, scale,
                                         block, 128).reshape(q.shape)
                return jnp.sum(jnp.sin(out)), out
            out = A.core_attention(q, k, v, window=window, sm_scale=scale, impl="xla")
            return jnp.sum(jnp.sin(out)), out
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got, want = grads(True), grads(False)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5)


def test_the_window_kernels_block_reaches_the_window_in_a_few_key_blocks():
    from galvatron_tpu.ops import window_attention as W

    assert W.BLOCK == 512 and W.block_for(8192, 512) == 512 and W.block_for(16384, 512) == 512
    assert W.block_for(8192 + 256, 512) == 256 and W.block_for(8192 + 128, 200) == 128  # the largest that divides
    assert W.block_for(8192, 512 * 3 + 1) == 512 and W.block_for(8192, 512 * 3 + 2) == 0  # three blocks before its own
    assert W.block_for(8192 + 128, 512) == 0  # 128-token blocks would need four before their own
    assert W.block_for(100, 16) == 0  # no whole 128-token tile


def test_auto_dispatch_takes_the_window_kernels_on_a_tpu_and_says_a_fallback_once(caplog):
    """On a TPU at a tileable length and heads of 128 `impl="auto"` takes the
    window kernels with k and v at their OWN heads; what falls back (heads of
    64) is logged, once a shape, with the window it names."""
    import logging

    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import window_attention

    calls = []

    def spy(q_, k_, v_, **kw):
        calls.append((q_.shape[2], k_.shape[2], kw["window"]))
        return A._xla_attention(q_, A.repeat_kv(k_, 2), A.repeat_kv(v_, 2), causal=True, sm_scale=kw["sm_scale"],
                                window=kw["window"])


    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b=1, s=256, nh=4, nkv=2, hd=128)
    narrow = _rand_qkv(jax.random.PRNGKey(6), b=1, s=256, nh=4, nkv=2, hd=64)
    A._FALLBACKS_SAID.clear()
    with forms.recording() as took, mock.patch.object(A, "_pallas_window", spy), \
         mock.patch.object(jax, "default_backend", lambda: "tpu"), \
         caplog.at_level(logging.WARNING, logger=A.__name__):
        out = A.core_attention(q, k, v, window=32)
        for _ in range(2):
            A.core_attention(*narrow, window=32)
        A.core_attention(q, k, v, window=32, impl="xla")  # asked for: not a fallback, nothing said
    assert calls == [(4, 2, 32)] and took == {forms.WINDOW_ATTENTION: {"pallas": 1, "xla": 3}}
    assert window_attention.block_for(256, 32) == 256
    np.testing.assert_allclose(np.asarray(out), np.asarray(A.core_attention(q, k, v, window=32, impl="xla")), atol=2e-5)
    said = [r.getMessage() for r in caplog.records if "XLA attention on a TPU" in r.getMessage()]
    assert len(said) == 1 and "a window of 32" in said[0] and "head_dim 64" in said[0] and "window kernel" in said[0]


@pytest.mark.parametrize("case,fields,kernels,tables,gates", [
    ("whole_head_half_split", {}, True, True, True),
    ("half_rope", {"window_partial_rotary_factor": 0.5}, True, False, True),
    ("no_head_gate", {"attn_head_gate": False}, True, True, False),
    ("head_dim_64", {"head_dim": 64}, False, False, False),
    ("a_bias", {}, False, False, False),
    ("impl_xla", {"attn_impl": "xla"}, False, False, False),
])
def test_a_window_layer_hands_the_kernels_what_they_fuse_and_keeps_the_rest(window_kernels_as_on_a_tpu, case, fields,
                                                                            kernels, tables, gates):
    """What `attention_mixer` hands the window call by what it observes: on a
    TPU at heads of 128 the kernels take q UNTURNED with the rotation's tables
    and the gate logits where the layer's rope is the half-split turn of whole
    heads; a rope on half a head keeps `apply_rotary` before the call (the gate
    still rides); a bias, heads of 64 or `impl="xla"` keep XLA's band, rope
    before it and the gate's product after it. Said to `obs/forms`, and the
    mixer's output the same either way."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.laguna import laguna_config
    from galvatron_tpu.ops import attention as A

    cfg = laguna_config(**{**dict(hidden_size=64, num_heads=2, window_num_heads=4, num_kv_heads=2, head_dim=128,
                                  ffn_hidden=32, dense_ffn_hidden=32, num_layers=5, vocab_size=128, max_seq_len=256,
                                  num_experts=8, experts_per_token=2, sliding_window=40, init_std=0.2,
                                  compute_dtype=jnp.float32), **fields})
    lcfg = cfg.layer_config("window.routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64))
    positions = jnp.arange(256)[None] + 3
    bias = jnp.zeros((1, 1, 1, 256)) if case == "a_bias" else None
    seen = []

    def spy(q_, k_, v_, **kw):  # the kernels' call, answered by XLA's band on what they would compute
        seen.append((kw.get("q_rope") is not None, kw.get("head_gate") is not None))
        if kw.get("q_rope") is not None:
            q_ = apply_rotary(q_, positions, lcfg.rope_theta)
        out = A._xla_attention(q_, A.repeat_kv(k_, 2), A.repeat_kv(v_, 2), causal=True, sm_scale=kw["sm_scale"],
                               window=kw["window"])
        return out if kw.get("head_gate") is None else out * jax.nn.sigmoid(kw["head_gate"])[..., None]

    run = lambda: M.MIXERS["window"].forward(  # noqa: E731
        lp, y, positions, lcfg, mesh=None, axes=None, attn_bias=bias, attn_sharding=None, return_kv=False)[0]
    want = run()  # the CPU's path
    with forms.recording() as took, mock.patch.object(A, "_pallas_window", spy), window_kernels_as_on_a_tpu():
        got = run()
    assert seen == ([(tables, gates)] if kernels else [])
    assert took == ({forms.WINDOW_ATTENTION: {"pallas": 1}, **({forms.WINDOW_OPERANDS: {"as_projected": 1}} if tables else {})}
                    if kernels else {forms.WINDOW_ATTENTION: {"xla": 1}})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("how", [dict(interleaved=True), dict(rotary_dim=64), dict(scaling={
    "rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 16, "beta_fast": 4, "beta_slow": 1,
    "attention_factor": 1.2})], ids=["interleaved", "half_rope", "yarn"])
def test_a_rotation_that_is_no_product_with_two_tables_has_none(how):
    """`half_split_tables` is `apply_rotary`'s half-split turn of whole heads
    at the plain frequencies as `x * cos + roll(x, half) * sin`, and None for
    every other rotation: the caller turns q itself then."""
    from galvatron_tpu.ops.rope import half_split_tables

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 128))
    positions = jnp.arange(24)[None] * jnp.array([[1], [5]])
    cos, sin = half_split_tables(positions, 128, 500.0)
    assert cos.dtype == sin.dtype == jnp.float32 and cos.shape == sin.shape == (2, 24, 128)
    turned = x * cos[:, :, None] + jnp.roll(x, 64, axis=-1) * sin[:, :, None]
    np.testing.assert_allclose(np.asarray(turned), np.asarray(apply_rotary(x, positions, 500.0)), atol=1e-6)
    assert half_split_tables(positions, 128, 500.0, rotary_dim=128) is not None
    assert half_split_tables(positions, 128, 500.0, **how) is None


def test_tables_and_gate_logits_ride_the_window_kernels_alone(window_kernels_as_on_a_tpu):
    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops.rope import half_split_tables

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, s=128, nh=2, hd=128)
    tables, logits = half_split_tables(jnp.arange(128)[None], 128), jnp.zeros((1, 128, 2))
    assert not A.window_takes_kernels(q.shape, k.shape, window=4)  # off a TPU
    with pytest.raises(ValueError, match="ride the window kernels alone"):
        A.core_attention(q, k, v, window=4, q_rope=tables)
    with pytest.raises(ValueError, match="ride the window kernels alone"):
        A.core_attention(q, k, v, window=4, head_gate=logits)
    with pytest.raises(ValueError, match="without a window the caller turns q"):
        A.core_attention(q, k, v, head_gate=logits)
    with window_kernels_as_on_a_tpu() as on_a_tpu:
        pass
    assert on_a_tpu(q.shape, k.shape, window=4) and not on_a_tpu(q.shape, k.shape, window=4, biased=True)
    assert not on_a_tpu(q.shape, k.shape, window=4, impl="xla") and not on_a_tpu((1, 100, 2, 128), k.shape, window=4)
    assert not on_a_tpu((1, 128, 2, 64), (1, 128, 2, 64), window=4)

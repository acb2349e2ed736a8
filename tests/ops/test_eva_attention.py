"""EVA attention (ops/eva_attention.py): the XLA form against a dense-mask float64 softmax written from the
equations, the Pallas pair (interpreted) against the XLA form, forward and every gradient, the pooling's
written backward against autodiff, window 0 as plain causal attention, a partial last window, and the dispatch."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import eva_attention as E
from galvatron_tpu.ops.attention import core_attention

pytestmark = [pytest.mark.parallel]


def operands(seed, b, s, nh, hd, spread=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (b, s, nh, hd), jnp.float32) for i in range(3))
    phi, mu = (jax.random.normal(ks[3 + i], (nh, hd), jnp.float32) * spread for i in range(2))
    return q, k, v, phi, mu


def dense(q, k, v, phi, mu, window, chunk, scale):
    """The equations with (S, S) and (S, S / c) masks, a batch row and head at a time, float64:
    -> (out, the pooled mass a query)."""
    q, k, v, phi, mu = (np.asarray(t, np.float64) for t in (q, k, v, phi, mu))
    b, s, nh, hd = q.shape
    kc, vc = k.reshape(b, s // chunk, chunk, nh, hd), v.reshape(b, s // chunk, chunk, nh, hd)
    a = np.einsum("bjcnd,nd->bjcn", kc, phi)
    a = np.exp(a - a.max(2, keepdims=True))
    a /= a.sum(2, keepdims=True)
    kp, vp = np.einsum("bjcn,bjcnd->bjnd", a, kc) + mu, np.einsum("bjcn,bjcnd->bjnd", a, vc)
    t, i, j = np.arange(s)[:, None], np.arange(s)[None], np.arange(s // chunk)[None]
    own = (i <= t) & (i >= (t // window) * window)  # the query's window up to itself
    far = j < (t // window) * (window // chunk)  # the chunks of every EARLIER window
    out, mass = np.zeros_like(q), np.zeros((b, nh, s))
    for row in range(b):
        for h in range(nh):
            scores = np.concatenate([np.where(own, q[row, :, h] @ k[row, :, h].T * scale, -np.inf),
                                     np.where(far, q[row, :, h] @ kp[row, :, h].T * scale, -np.inf)], axis=1)
            p = np.exp(scores - scores.max(1, keepdims=True))
            p /= p.sum(1, keepdims=True)
            out[row, :, h] = p[:, :s] @ v[row, :, h] + p[:, s:] @ vp[row, :, h]
            mass[row, h] = p[:, s:].sum(1)
    return out, mass


@pytest.mark.parametrize("seq,why", [(256, "four whole windows"), (96, "one window and a half"), (64, "one window"),
                                     (40, "less than a window"), (136, "two windows and a chunk")])
def test_the_xla_form_is_the_dense_mask_softmax(seq, why):
    """Windows of 64 over chunks of 8, 2 rows of 2 heads of 16: the window-at-a-time form against every query on
    every key and pooled chunk under the two masks, float64. A last, partial window is a shorter window."""
    q, k, v, phi, mu = operands(0, 2, seq, 2, 16)
    with jax.default_matmul_precision("highest"):
        out, mass = E.eva_attention(q, k, v, phi, mu, window=64, chunk=8, sm_scale=0.25)
    want, want_mass = dense(q, k, v, phi, mu, 64, 8, 0.25)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-6)  # float32 sums of up to 64 + 24 terms
    np.testing.assert_allclose(np.asarray(mass), want_mass, atol=1e-6)
    assert (np.asarray(mass)[:, :, :64] == 0).all()  # window 0 sees no pooled key
    if seq > 64:
        assert float(mass[:, :, 64:].min()) > 0.0


def test_window_zero_is_plain_causal_attention():
    q, k, v, phi, mu = operands(1, 2, 64, 2, 16)
    with jax.default_matmul_precision("highest"):
        out, mass = E.eva_attention(q, k, v, phi, mu, window=64, chunk=8, sm_scale=0.25)
        causal = core_attention(q, k, v, causal=True, sm_scale=0.25, impl="xla")
        first, _ = E.eva_attention(*operands(0, 2, 256, 2, 16), window=64, chunk=8, sm_scale=0.25)
        q2, k2, v2, _, _ = operands(0, 2, 256, 2, 16)
        causal2 = core_attention(q2[:, :64], k2[:, :64], v2[:, :64], causal=True, sm_scale=0.25, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(causal), atol=2e-6)
    np.testing.assert_allclose(np.asarray(first[:, :64]), np.asarray(causal2), atol=2e-6)  # and of a longer sequence
    assert float(jnp.max(mass)) == 0.0


def plain_pooled(k, v, phi, mu, chunk):
    b, s, nh, hd = k.shape
    kc, vc = k.reshape(b, s // chunk, chunk, nh, hd), v.reshape(b, s // chunk, chunk, nh, hd)
    a = jax.nn.softmax(jnp.einsum("bjcnd,nd->bjcn", kc, phi), axis=2)
    return jnp.einsum("bjcn,bjcnd->bjnd", a, kc) + mu, jnp.einsum("bjcn,bjcnd->bjnd", a, vc)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_poolings_written_backward_is_autodiffs(chunk):
    """`_pooled_bwd` (written so that no float32 copy of k or v outlives a pass) against autodiff of the two
    einsums, every operand's cotangent, under cotangents of both outputs."""
    _, k, v, phi, mu = operands(2, 2, 64, 3, 16)
    wk, wv = (jax.random.normal(jax.random.PRNGKey(9 + i), (2, 64 // chunk, 3, 16)) for i in range(2))

    def loss(fn):
        return lambda *o: sum(jnp.sum(t * w) for t, w in zip(fn(*o), (wk, wv)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *o: E.pooled(*o, chunk=chunk)), argnums=(0, 1, 2, 3))(k, v, phi, mu)
        want = jax.grad(loss(lambda *o: plain_pooled(*o, chunk)), argnums=(0, 1, 2, 3))(k, v, phi, mu)
        np.testing.assert_allclose(np.asarray(E.pooled(k, v, phi, mu, chunk=chunk)[0]),
                                   np.asarray(plain_pooled(k, v, phi, mu, chunk)[0]), atol=2e-6)
    for name, g, w in zip(("k", "v", "phi", "mu"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5 * float(jnp.max(jnp.abs(w))), err_msg=name)


def kernel_loss(impl, window, chunk, score_dtype=jnp.float32):
    def loss(q, k, v, phi, mu):
        kp, vp = E.pooled(k, v, phi, mu, chunk=chunk)
        with pytest.MonkeyPatch.context() as patch:  # (the control's rounding: read as the call is traced)
            patch.setattr(E, "_SCORES", score_dtype)
            out, mass = E.aggregate(q, k, v, kp, vp, window=window, chunk=chunk, sm_scale=q.shape[3] ** -0.5, impl=impl)
        return jnp.sum(out * jnp.cos(0.01 * jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape))), (out, mass)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))


@pytest.mark.parametrize("seq,window,chunk,why", [
    (3072, 1024, 8, "three windows of two query blocks: the own-window loop, the pooled loop, the sums held in VMEM"),
    (1024, 512, 4, "two windows of one block"),
    (1024, 1024, 8, "one window: no pooled key is met"),
    (1536, 768, 6, "a window of three blocks of 256"),
])
def test_the_kernels_are_the_xla_form(seq, window, chunk, why):
    """Heads of 128, float32 operands (interpret mode): the forward, the pooled mass and EVERY gradient, phi's and
    mu's through the kernel's cotangents of the pooled keys and values among them, against the XLA form."""
    args = operands(3, 1, seq, 2, 128, spread=0.1)
    assert E.fits(args[0].shape, window, chunk)
    with pltpu.force_tpu_interpret_mode():
        (_, (out, mass)), grads = kernel_loss("pallas", window, chunk)(*args)
    (_, (want, want_mass)), want_grads = kernel_loss("xla", window, chunk)(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(np.asarray(mass), np.asarray(want_mass), atol=1e-6)
    for name, g, w in zip(("q", "k", "v", "phi", "mu"), grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-6 * float(jnp.max(jnp.abs(w))), err_msg=name)


def test_scores_in_bfloat16_are_told_from_float32_scores():
    """The chip check's control: the same call with its scores rounded to bfloat16 before the softmax moves the
    output a thousand times further than the float32 forms differ from one another."""
    args = operands(3, 1, 1024, 2, 128, spread=0.1)
    (_, (want, _)), _ = kernel_loss("xla", 512, 4)(*args)
    (_, (rounded, _)), _ = kernel_loss("xla", 512, 4, jnp.bfloat16)(*args)
    with pltpu.force_tpu_interpret_mode():
        (_, (kernel_rounded, _)), _ = kernel_loss("pallas", 512, 4, jnp.bfloat16)(*args)
    assert float(jnp.max(jnp.abs(rounded - want))) > 2e-3
    assert float(jnp.max(jnp.abs(kernel_rounded - want))) > 2e-3


def test_the_form_is_decided_by_what_the_call_observes_and_said():
    q, k, v, phi, mu = operands(4, 1, 128, 2, 16)
    with forms.recording() as took:
        E.eva_attention(q, k, v, phi, mu, window=64, chunk=8, sm_scale=0.25)
    assert took == {forms.EVA_ATTENTION: {"xla": 1}}  # off a TPU: the XLA form
    with pytest.raises(ValueError, match="no multiples of the chunk"):
        E.eva_attention(q[:, :100], k[:, :100], v[:, :100], phi, mu, window=64, chunk=8, sm_scale=0.25)
    with pytest.raises(ValueError, match="the kernels take heads of whole 128-lane tiles"):
        kp, vp = E.pooled(k, v, phi, mu, chunk=8)
        E.aggregate(q, k, v, kp, vp, window=64, chunk=8, sm_scale=0.25, impl="pallas")


@pytest.mark.parametrize("shape,window,chunk,fits", [
    ((1, 8192, 32, 128), 2048, 16, True), ((4, 32768, 32, 128), 2048, 16, True),
    ((1, 8192, 32, 64), 2048, 16, False),  # heads that are no whole lane tile
    ((1, 9216, 32, 128), 2048, 16, False),  # a partial last window
    ((1, 8192, 32, 128), 2048, 32, False),  # 64 pooled keys a window: no whole tile
    ((1, 1920, 32, 128), 1920, 15, True),  # a window of 128 x 15: blocks of 128
    ((1, 2000, 32, 128), 2000, 10, False),  # no block of 128 or more divides the window
])
def test_which_shapes_the_kernels_take(shape, window, chunk, fits):
    assert E.fits(shape, window, chunk) is fits
    assert E.block_for(2048) == 512 and E.block_for(768) == 256 and E.block_for(1920) == 128 and E.block_for(100) == 0

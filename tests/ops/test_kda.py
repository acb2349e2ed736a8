"""Kimi Delta Attention's per-channel rule (`ops/linear_attention.kda_rule`), the XLA form against the recurrence
in float64 (operands, oracles and tolerances: tests/ops/kda_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import linear_attention as LA
from tests.ops.kda_cases import KERNEL, in_float64, operands, recurrence, scalar_of, xla_rule


@pytest.mark.parametrize("strong", [False, True], ids=["gates", "gates_to_-30"])
@pytest.mark.parametrize("seq", [128, 100, 64, 17], ids=lambda s: "s%d" % s)
def test_the_chunked_rule_is_the_recurrence(seq, strong):
    """Outputs, final states and all five gradients, at lengths that are and
    are not whole chunks (a rest is padded with tokens that neither forget
    nor write), finite and equal where some channels forget everything."""
    args = operands(seq, seq, strong=strong)
    (o, last), (ref_o, ref_last) = LA.kda_rule(*args), in_float64(recurrence)(*args)
    assert o.shape == ref_o.shape and o.dtype == jnp.float32 and bool(jnp.all(jnp.isfinite(o)))
    assert float(jnp.max(jnp.abs(o - ref_o))) < 5e-6
    assert float(jnp.max(jnp.abs(last - ref_last))) < (1e-4 if strong else 5e-6)
    grads = jax.grad(scalar_of(LA.kda_rule), argnums=(0, 1, 2, 3, 4))(*args)
    ref_grads = in_float64(jax.grad(scalar_of(recurrence), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, a, b in zip("q k v g beta".split(), grads, ref_grads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(jnp.max(jnp.abs(b))), name


def test_a_gate_equal_over_the_channels_is_the_gated_delta_rule():
    q, k, v, g, beta = operands(3, 128)
    scalar = g[..., 0]
    o, last = LA.kda_rule(q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta)
    ref_o, ref_last = LA.gated_delta_rule(q, k, v, scalar, beta, impl="xla")
    assert float(jnp.max(jnp.abs(o - ref_o))) < 1e-6 and float(jnp.max(jnp.abs(last - ref_last))) < 1e-6


def test_the_decayed_products_never_leave_float32s_range():
    """`_channel_products` against the decays formed whole in float64: where
    `(k_t e^{G_t}) . (k_j e^{-G_j})` would overflow (G to -1900 a chunk), the
    sub-blocks' exponents stay <= 0."""
    q, k, _, g, _ = operands(5, 64, batch=1, heads=1, strong=True)
    q, k, total = q[0, :, 0], k[0, :, 0], jnp.cumsum(g[0, :, 0], axis=0)
    assert float(jnp.min(total)) < -800.0  # e^{-G} is past float32
    kk, qk = LA._channel_products(q, k, total)
    t64 = np.asarray(total, np.float64)
    decay = np.where(np.tril(np.ones((64, 64), bool))[..., None],
                     np.exp(np.minimum(t64[:, None, :] - t64[None, :, :], 0.0)), 0.0)
    for got, left in ((kk, k), (qk, q)):
        want = np.einsum("tc,jc,tjc->tj", np.asarray(left, np.float64), np.asarray(k, np.float64), decay)
        assert bool(jnp.all(jnp.isfinite(got))) and float(np.max(np.abs(got - want))) < 1e-6
    assert float(jnp.max(jnp.abs(jnp.triu(kk, 1)))) == 0.0


def test_bf16_operands_give_bf16_outputs_and_a_float32_state():
    q, k, v, g, beta = operands(7, 128)
    o, last = LA.kda_rule(*(t.astype(jnp.bfloat16) for t in (q, k, v)), g, beta)
    ref_o, ref_last = in_float64(recurrence)(q, k, v, g, beta)
    assert o.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - ref_o))) < 0.05
    assert float(jnp.max(jnp.abs(last - ref_last))) < 0.05


def test_off_a_tpu_the_choice_is_the_xla_form_and_it_is_counted():
    ops = operands(1, 64)  # heads of 32 x 16: no kernel could take them
    with forms.recording() as took:
        got = LA.kda_rule(*ops)
        wide = operands(1, 128, **KERNEL)  # the kernels' widths, but this is a CPU
        LA.kda_rule(*wide)
    assert took == {forms.KDA_RULE: {"xla": 2}}  # (the scalar rule's part is its own)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(xla_rule(*ops)[0]))

"""Kimi Delta Attention's rule in its chunked form (ops/linear_attention.py
`kda_rule`: sub-blocks of 16 tokens, the later block's first token the
reference between blocks) against the recurrence token by token in float64.

Tolerances, and why. Both are the same arithmetic in another order; the
chunked form is float32 (the gate's running sums, the decays, the triangular
solve, the carried state). Outputs and states agree to a few 1e-7 absolute on
values of order 1, gradients to a few 1e-6 of the leaf's largest entry
(measured: 5e-6 worst with ordinary gates, 2e-5 with gates of -30 a token,
whose running sums reach -1900 and are subtracted from each other); the
limits are 5e-6 and 1e-4."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import enable_x64

from galvatron_tpu.ops import linear_attention as LA


def in_float64(f):
    """`f` under `enable_x64`, its results as numpy: float64 for this call alone."""
    def wrapped(*args):
        with enable_x64():
            return jax.tree.map(np.asarray, f(*(np.asarray(a, np.float64) for a in args)))
    return wrapped


def recurrence(q, k, v, g, beta):
    """S' = Diag(e^g) S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q, in
    the operands' dtype (float64 under `in_float64`)."""
    b, _, h, dk = q.shape

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(jnp.asarray(t), 1, 0) for t in (q, k, v, g, beta))
    last, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1]), xs[0].dtype), xs)
    return jnp.moveaxis(o, 0, 1), last


def operands(seed, seq, *, batch=2, heads=2, dk=32, dv=16, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (batch, seq, heads, dk), jnp.float32)) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, heads, dk), jnp.float32))
    v = jax.random.normal(ks[2], (batch, seq, heads, dv), jnp.float32)
    g = -jnp.exp(jax.random.uniform(ks[3], (batch, seq, heads, dk), jnp.float32, np.log(1e-3), np.log(1.5)))
    if strong:
        # every fourth channel forgets down to e^-30 a token, the next hardly at all
        lane = jnp.arange(dk) % 4
        g = jnp.where(lane == 0, -30.0 * jax.random.uniform(ks[5], g.shape),
                      jnp.where(lane == 1, -1e-4, g))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads), jnp.float32))
    return q, k, v, g, beta


def scalar_of(rule):
    def f(*args):
        o, last = rule(*args)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.square(last))
    return f


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

"""The chunked gated delta rule and the causal convolution
(ops/linear_attention.py) on the CPU in float32, against the recurrence run
token by token and against shifted adds, each written here in a few lines.

Tolerances, and why: in float32 the chunked form does the recurrence's
arithmetic in another order (a chunk's triangular solve and batched matmuls
against 16 to 320 dependent rank-one updates): measured worst relative error
3e-6 of the output's largest magnitude, 5e-6 of a gradient's; the limit is
5e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.ops import linear_attention as L

TOL = 5e-5
B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def recurrence(q, k, v, g, beta):
    """The rule token by token: S' = e^g S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q."""
    serves = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, serves, axis=2), jnp.repeat(k, serves, axis=2)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None, None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(token, jnp.zeros((v.shape[0], v.shape[2], q.shape[-1], v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1), state


def operands(tokens, seed=0, hv=HV):
    """Unit keys, queries / sqrt(d_k), decays from 1e-3 to 1.6 a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, tokens, HK, DK))) / DK ** 0.5
    k = unit(jax.random.normal(ks[1], (B, tokens, HK, DK)))
    v = jax.random.normal(ks[2], (B, tokens, hv, DV))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, tokens, hv), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, tokens, hv)))
    return q, k, v, g, beta


def objective(rule):
    return lambda *ops: jnp.sum(jnp.sin(rule(*ops)[0]))


@pytest.mark.parametrize("chunks", [2, 5])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_chunked_rule_is_the_recurrence(chunk, chunks):
    ops = operands(chunk * chunks, seed=chunk + chunks)
    with jax.default_matmul_precision("highest"):
        o, state = L.gated_delta_rule(*ops, chunk=chunk)
        want_o, want_state = recurrence(*ops)
        grads = jax.grad(objective(lambda *a: L.gated_delta_rule(*a, chunk=chunk)), range(5))(*ops)
        want = jax.grad(objective(recurrence), range(5))(*ops)
    assert o.shape == want_o.shape and state.shape == (B, HV, DK, DV)
    assert float(jnp.max(jnp.abs(o - want_o))) < TOL * float(jnp.max(jnp.abs(want_o)))
    assert float(jnp.max(jnp.abs(state - want_state))) < TOL * float(jnp.max(jnp.abs(want_state)))
    for name, got, ref in zip("q k v g beta".split(), grads, want):
        assert float(jnp.max(jnp.abs(got - ref))) < TOL * float(jnp.max(jnp.abs(ref))), name


def test_equal_neighbouring_keys_do_not_break_the_solve():
    """A run of one repeated key with beta near 1 and no decay: I + A is the
    all-ones lower triangle, whose inverse is bidiagonal while the powers of A
    grow as binomials (the product form of the inverse loses every digit)."""
    tokens = 64
    q, k, v, g, beta = operands(tokens, seed=9)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-6), jnp.full_like(beta, 0.999)
    with jax.default_matmul_precision("highest"):
        o, _ = L.gated_delta_rule(q, k, v, g, beta, chunk=64)
        want, _ = recurrence(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(o - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused_by_name():
    with pytest.raises(ValueError, match="100 tokens is no multiple of the chunk of 64"):
        L.gated_delta_rule(*operands(100))


def test_the_unit_lower_inverse_is_the_inverse():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 64, 64)), -1) * 0.3
    with jax.default_matmul_precision("highest"):
        inverse = L.unit_lower_inverse(a)
        product = jnp.matmul(jnp.eye(64) + a, inverse)
    np.testing.assert_allclose(np.asarray(product), np.broadcast_to(np.eye(64), product.shape), atol=2e-5)
    assert float(jnp.max(jnp.abs(jnp.triu(inverse, 1)))) == 0.0


@pytest.mark.parametrize("taps", [1, 4])
def test_the_convolution_is_shifted_adds(taps):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 6))
    w = jax.random.uniform(jax.random.PRNGKey(2), (6, taps), minval=-0.5, maxval=0.5)
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[1]):
        for j in range(taps):  # tap j reads the token (taps - 1 - j) back; none before the start
            if t - (taps - 1 - j) >= 0:
                want[:, t] += np.asarray(w[:, j]) * np.asarray(x[:, t - (taps - 1 - j)])
    np.testing.assert_allclose(np.asarray(L.causal_conv(x, w)), want, atol=1e-6)
    # causal: a later token moves no earlier output
    moved = L.causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(moved[:, :7]), np.asarray(L.causal_conv(x, w)[:, :7]))

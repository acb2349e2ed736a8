"""Mamba-2's chunked scan (ops/ssd.py) on the CPU in float32, against the
recurrence run token by token, written here in a few lines.

Tolerances, and why: in float32 the chunked form does the recurrence's
arithmetic in another order (a chunk's decay mask `exp(G_t - G_j)` and batched
matmuls against 40 to 200 dependent multiply-adds of the state). The running
sum `G` of `dt A` reaches -30 inside a chunk at these decays, so a difference
of two of its values carries `30 x 6e-8 = 2e-6` of absolute error into the
exponent: measured worst error 4e-6 of the output's largest magnitude, 6e-6
of a gradient's (the gradients of `A` and `dt`, sums of such differences over
all tokens, are the worst); the limit is 5e-5. The same core with the carried
state rounded to bfloat16 a chunk lies 1e-3 off: twenty times the limit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.ops.ssd import CHUNK, HEADS_AT_ONCE, ssd_scan

TOL = 5e-5
B, H, P, N = 2, 4, 8, 16


def recurrence(x, dt, a, bm, cm, d):
    """h = exp(dt A) h + dt x B^T; y = h C + D x, token by token."""
    def token(state, t):
        xt, dtt, bt, ct = t
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return state, jnp.einsum("bhps,bs->bhp", state, ct) + d[:, None] * xt

    ts = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm))
    state, y = jax.lax.scan(token, jnp.zeros((x.shape[0], H, P, N)), ts)
    return jnp.moveaxis(y, 0, 1), state


def operands(tokens, seed=0):
    """Decays exp(dt A) from 0.2 to 0.999 a token over the heads, as the
    Mamba-2 initialisation gives them: dt log-uniform in [1e-3, 0.1], A in [1, 16]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, tokens, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, tokens, H), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    bm, cm = (jax.random.normal(k, (B, tokens, N)) for k in ks[3:5])
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a, bm, cm, d


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("tokens,chunk", [(64, 16), (64, 64), (50, 16), (37, 10), (200, 64), (48, CHUNK)],
                         ids=["4_chunks", "1_chunk", "50_by_16", "37_by_10", "200_by_64", "shorter_than_a_chunk"])
def test_the_chunked_scan_is_the_recurrence(tokens, chunk):
    """Chunks that do and do not divide the length (the rest is padded with
    dt = 0 and cut off), outputs and final states."""
    ops = operands(tokens)
    with jax.default_matmul_precision("highest"):
        y, last, peak = jax.jit(lambda *o: ssd_scan(*o, chunk=chunk, heads_at_once=2))(*ops)
        want_y, want_last = recurrence(*ops)
    assert y.shape == want_y.shape and last.shape == (B, H, P, N)
    assert worst(y, want_y) < TOL and worst(last, want_last) < TOL
    assert float(peak) >= float(jnp.max(jnp.abs(want_last))) * (1 - TOL)


@pytest.mark.parametrize("tokens,chunk", [(64, 16), (50, 16)], ids=["divides", "does_not_divide"])
def test_every_gradient_is_the_recurrences(tokens, chunk):
    """x, dt, A, B, C and D, through the kept chunk-start states and the
    recomputation within a chunk; A_log and dt_bias reach the scan as A and dt."""
    ops = operands(tokens, seed=3)
    weights = jax.random.normal(jax.random.PRNGKey(9), (B, tokens, H, P))

    def through(scan):
        def loss(x, dt_raw, a_log, bm, cm, d):
            y, last = scan(x, jax.nn.softplus(dt_raw), -jnp.exp(a_log), bm, cm, d)[:2]
            return jnp.sum(weights * y) + jnp.sum(jnp.sin(last))

        return jax.jit(jax.grad(loss, argnums=tuple(range(6))))

    x, dt, a, bm, cm, d = ops
    raw = (x, jnp.log(jnp.expm1(dt)), jnp.log(-a), bm, cm, d)
    with jax.default_matmul_precision("highest"):
        got = through(lambda *o: ssd_scan(*o, chunk=chunk, heads_at_once=2))(*raw)
        want = through(recurrence)(*raw)
    for name, g, w in zip(("x", "dt_bias", "A_log", "B", "C", "D"), got, want):
        assert worst(g, w) < TOL, (name, worst(g, w))


def test_state_crosses_the_chunk_edges():
    """With the state dropped at every chunk's start the outputs past the
    first chunk are far off: what the scan carries is in the result."""
    ops = operands(64)
    with jax.default_matmul_precision("highest"):
        y = ssd_scan(*ops, chunk=16)[0]
        alone = jnp.concatenate([ssd_scan(*(t[:, i:i + 16] if t.ndim > 1 else t for t in ops), chunk=16)[0]
                                 for i in range(0, 64, 16)], axis=1)
    assert worst(alone[:, :16], y[:, :16]) < TOL
    assert worst(alone[:, 16:], y[:, 16:]) > 0.05


def test_a_bfloat16_state_fails_the_tolerance():
    """The control: the same core with the carried state rounded to bfloat16 a
    chunk is outside the limit the float32 state meets."""
    ops = operands(200)
    with jax.default_matmul_precision("highest"):
        want_y, want_last = recurrence(*ops)
        y32, last32, _ = ssd_scan(*ops, chunk=16)
        y16, last16, _ = ssd_scan(*ops, chunk=16, state_dtype=jnp.bfloat16)
    assert worst(y32, want_y) < TOL and worst(last32, want_last) < TOL
    assert worst(y16, want_y) > 10 * TOL and worst(last16, want_last) > 10 * TOL


@pytest.mark.parametrize("at_once", [1, 2, 3, 4, HEADS_AT_ONCE])
def test_the_heads_worked_at_once_do_not_change_the_result(at_once):
    """`heads_at_once` takes the largest divisor of the heads up to it (3 of 4
    heads: 2), and a group's result is its heads' own."""
    ops = operands(64)
    with jax.default_matmul_precision("highest"):
        y, last, _ = ssd_scan(*ops, chunk=16, heads_at_once=at_once)
        want_y, want_last = recurrence(*ops)
    assert worst(y, want_y) < TOL and worst(last, want_last) < TOL


def test_bfloat16_operands_keep_a_float32_state():
    """bf16 x, B, C (the compute dtype of a bf16 model): the output comes back
    in bf16, the final state in float32 and within bf16 rounding of the
    recurrence on the same operands (2^-8 of its magnitude)."""
    x, dt, a, bm, cm, d = operands(128)
    x16, b16, c16 = (t.astype(jnp.bfloat16) for t in (x, bm, cm))
    y, last, _ = ssd_scan(x16, dt, a, b16, c16, d, chunk=32)
    with jax.default_matmul_precision("highest"):
        want_y, want_last = recurrence(*(t.astype(jnp.float32) for t in (x16, dt, a, b16, c16, d)))
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert worst(last, want_last) < 1e-4  # float32 operands at the highest precision
    assert worst(y.astype(jnp.float32), want_y) < 2 ** -6

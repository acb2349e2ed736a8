"""Kimi Delta Attention's rule in its chunked form (ops/linear_attention.py
`kda_rule`: sub-blocks of 16 tokens, the later block's first token the
reference between blocks) against the recurrence token by token in float64.

Tolerances, and why. Both are the same arithmetic in another order; the
chunked form is float32 (the gate's running sums, the decays, the triangular
solve, the carried state). Outputs and states agree to a few 1e-7 absolute on
values of order 1, gradients to a few 1e-6 of the leaf's largest entry
(measured: 5e-6 worst with ordinary gates, 2e-5 with gates of -30 a token,
whose running sums reach -1900 and are subtracted from each other); the
limits are 5e-6 and 1e-4.

The kernel form (`kda_fwd`, `kda_bwd`: what a TPU runs) is interpreted here
at the widths it needs (d_k = d_v = 128), against the recurrence AND the XLA
form. Float32: every exponent is a partial sum of g made by one product, no
difference of two running sums, so it lies nearer the recurrence than the XLA
form does (measured 6e-7 of the largest entry worst, gates of -30 a token
among it; the XLA form 5e-6); the limit is 1e-5. bf16 operands: the products
on the way to the output round in both forms, in other places (the kernels
round every level's `q . E` and `k . E`, the XLA form keeps its diagonal
blocks float32): measured 5.8e-3 against the XLA form's 4.0e-3 on the same
operands, so no further from the recurrence than twice the XLA form is, or
inside the float32 limit where both are (the final states)."""

import jax
import jax.numpy as jnp
import numpy as np

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


KERNEL = dict(batch=1, heads=2, dk=128, dv=128)


def kernel_rule(*ops, **kw):
    return LA.kda_rule(*ops, impl="pallas", **kw)


def xla_rule(*ops):
    return LA.kda_rule(*ops, impl="xla")


def worst(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

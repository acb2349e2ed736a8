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
import pytest

from jax import enable_x64
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.ops import linear_attention as LA
from galvatron_tpu.ops.attention import KernelSharding


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


# --- the kernel form, interpreted -------------------------------------------

KERNEL = dict(batch=1, heads=2, dk=128, dv=128)
KERNEL_TOL = 1e-5
LEAVES = "o states dq dk dv dg dbeta".split()


def kernel_rule(*ops, **kw):
    return LA.kda_rule(*ops, impl="pallas", **kw)


def xla_rule(*ops):
    return LA.kda_rule(*ops, impl="xla")


def worst(got, want):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def with_gradients(rule, ops):
    """o, the final states and the five gradients of a scalar that reads both."""
    def of(*a):
        o, last = rule(*a)
        o = o.astype(jnp.float32) if o.dtype == jnp.bfloat16 else o
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.square(last))
    return tuple(rule(*ops)) + tuple(jax.grad(of, argnums=(0, 1, 2, 3, 4))(*ops))


def interpreted(rule, ops):
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        return with_gradients(rule, ops)


@pytest.mark.parametrize("seq", [128, 384, 300], ids=["one_tile", "three_tiles", "s300_padded"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_are_the_recurrence_and_the_xla_form(dtype, seq, monkeypatch):
    """o, the final states and all five gradients. Two tiles a grid step: three
    tiles are a whole block and one that is not, one tile is less than a
    block, and 300 tokens are three tiles with 84 padded tokens behind."""
    monkeypatch.setattr(LA, "_BLOCK", 2)
    ops = operands(seq, seq, **KERNEL)
    cast = tuple(x.astype(dtype) for x in ops[:3]) + ops[3:]
    exact = tuple(x.astype(jnp.float32) for x in cast)
    got, xla = interpreted(kernel_rule, cast), with_gradients(xla_rule, cast)
    want = in_float64(lambda *a: with_gradients(recurrence, a))(*exact)
    assert got[0].dtype == dtype and got[0].shape == want[0].shape
    assert got[1].dtype == jnp.float32 and got[1].shape == (1, 2, 128, 128)
    for name, g, x, w in zip(LEAVES, got, xla, want):
        assert g.shape == w.shape and bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        limit = KERNEL_TOL if dtype == jnp.float32 else max(KERNEL_TOL, 2 * worst(x, w))
        assert worst(g, w) <= limit, (name, worst(g, w), worst(x, w))
        assert worst(g, x) <= 2 * limit, (name, worst(g, x))


def test_the_kernels_hold_channels_that_forget_everything_beside_channels_that_forget_nothing():
    """A gate down to -30 a token in every fourth channel, -1e-4 in the next:
    a tile's running sums pass -1900, `e^{-G}` is past float32, and every
    exponent the kernels form is a sum of g's, <= 0: finite, and the
    recurrence."""
    ops = operands(11, 256, strong=True, **KERNEL)
    assert float(jnp.min(jnp.sum(ops[3][:, :128], axis=1))) < -800.0
    got = interpreted(kernel_rule, ops)
    want = in_float64(lambda *a: with_gradients(recurrence, a))(*ops)
    for name, g, w in zip(LEAVES, got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert worst(g, w) <= KERNEL_TOL, (name, worst(g, w))


def test_the_kernels_solve_a_run_of_one_repeated_key():
    """`I + A` the all-ones lower triangle of a tile of 128 (beta near 1, next
    to no decay): through the kernels' elimination and merges."""
    q, k, v, g, beta = operands(9, 128, **KERNEL)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-6), jnp.full_like(beta, 0.999)
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, _ = kernel_rule(q, k, v, g, beta)
    want, _ = in_float64(recurrence)(q, k, v, g, beta)
    assert worst(o, want) < 1e-4


def test_the_kernels_on_a_gate_equal_over_the_channels_are_the_scalar_rules_kernels():
    q, k, v, g, beta = operands(3, 256, **KERNEL)
    scalar = g[..., 0]
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = with_gradients(kernel_rule, (q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta))
        want = with_gradients(lambda *a: LA.gated_delta_rule(*a, impl="pallas"), (q, k, v, scalar, beta))
    for name, a, b in zip(LEAVES, got, want):
        if name == "dg":  # a channel's share each; the scalar gate's gradient is their sum
            a = jnp.sum(a, axis=-1)
        assert worst(a, b) <= KERNEL_TOL, (name, worst(a, b))


def test_the_kernels_run_a_device_on_its_rows_of_the_batch():
    """Under `sharding` the kernels sit in a manual region over the batch: two
    devices, a row each, the same numbers as one device on both."""
    ops = operands(3, 128, **dict(KERNEL, batch=2))
    sharding = KernelSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kernel_rule(*a, sharding=sharding))(*ops)
        want_o, want_state = kernel_rule(*ops)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-6)


def test_off_a_tpu_the_choice_is_the_xla_form_and_it_is_counted():
    ops = operands(1, 64)  # heads of 32 x 16: no kernel could take them
    before = dict(LA.TOOK)
    got = LA.kda_rule(*ops)
    wide = operands(1, 128, **KERNEL)  # the kernels' widths, but this is a CPU
    LA.kda_rule(*wide)
    assert LA.TOOK["kda_xla"] == before.get("kda_xla", 0) + 2
    assert LA.TOOK["kda_pallas"] == before.get("kda_pallas", 0)
    assert LA.TOOK["xla"] == before.get("xla", 0)  # the scalar rule's count is its own
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(xla_rule(*ops)[0]))

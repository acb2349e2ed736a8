"""A delta-rule mixer between its projections as ONE rule through the kernels (`kernel_mixer`, `kda_kernel_mixer`),
interpreted, against the XLA form and on a mesh (operands and oracles: tests/ops/linear_attention_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.ops import linear_attention as L
from galvatron_tpu.ops.attention import KernelSharding
from tests.ops.linear_attention_cases import (EPS, SMALL_LAYOUTS, TOL, around, per_channel, worst, xla_after,
                                              xla_before, xla_gate)


def mixer_operands(layout, tokens, seed=0):
    """What the mixer's rule takes, in its order: the scalar rule's (qkvz,
    taps, scale, g, beta), the per-channel rule's (qkv, taps, scale, f,
    dt_bias, a_log, z, beta)."""
    given = around(layout, tokens, jnp.float32, seed=seed, batch=2)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    heads = layout.heads.value_heads
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], (2, tokens, heads)))
    if per_channel(layout):
        return (given["x"], given["taps"], given["scale"], given["f"], given["dt_bias"], given["a_log"],
                given["within"], beta)
    g = -jnp.exp(jax.random.uniform(ks[0], (2, tokens, heads), minval=np.log(1e-3), maxval=np.log(1.6)))
    return given["x"], given["taps"], given["scale"], g, beta


def xla_mixer(layout, *ops):
    heads, (b, s, _) = layout.heads, ops[0].shape
    by_heads = lambda t, n, d: t.reshape(b, s, n, d)  # noqa: E731
    q, k, v = xla_before(layout, ops[0], ops[1])
    q, k = by_heads(q, heads.key_heads, heads.d_k), by_heads(k, heads.key_heads, heads.d_k)
    v = by_heads(v, heads.value_heads, heads.d_v)
    if per_channel(layout):
        x, taps, scale, f, dt_bias, a_log, z, beta = ops
        o, state = L.kda_rule(q, k, v, by_heads(xla_gate(layout, f, dt_bias, a_log), heads.key_heads, heads.d_k),
                              beta, impl="xla")
        return xla_after(layout, o.reshape(b, s, -1), z, scale), state
    x, taps, scale, g, beta = ops
    o, state = L.gated_delta_rule(q, k, v, g, beta, impl="xla")
    return xla_after(layout, o.reshape(b, s, -1), x, scale), state


def kernel_mixer(layout, **where):
    """The mixer's rule through the kernels -> (out, states)."""
    if per_channel(layout):
        return lambda *a: L.kda_kernel_mixer(*a, layout, eps=EPS, **where)[:2]
    return lambda *a: L.kernel_mixer(*a, layout, eps=EPS, **where)


def mixer_objective(rule):
    def of(*a):
        out, states = rule(*a)
        return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(states))
    return of


NAMES = {False: "out states dqkvz dtaps dscale dg dbeta".split(),
         True: "out states dqkv dtaps dscale df ddt_bias da_log dz dbeta".split()}


@SMALL_LAYOUTS
def test_the_kernel_mixer_is_the_xla_form_through_the_core(layout, monkeypatch):
    """Convolution and norms, (the per-channel gate,) the core's kernels, the
    gated norm as ONE rule (`kernel_mixer`, `kda_kernel_mixer`): the result,
    the final states and the gradients to every operand, the cotangent of the
    projection's output written once."""
    monkeypatch.setattr(L, "_TOKENS", 128)
    ops = mixer_operands(layout, 256)
    kernel = kernel_mixer(layout)
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = kernel(*ops) + jax.grad(mixer_objective(kernel), range(len(ops)))(*ops)
        xla = lambda *a: xla_mixer(layout, *a)  # noqa: E731
        want = xla(*ops) + jax.grad(mixer_objective(xla), range(len(ops)))(*ops)
        if per_channel(layout):  # the counter: the mean of exp(g) a row of the batch
            decay = L.kda_kernel_mixer(*ops, layout, eps=EPS)[2]
            np.testing.assert_allclose(np.asarray(decay), np.asarray(jnp.mean(jnp.exp(xla_gate(
                layout, *ops[3:6])), axis=(1, 2))), rtol=1e-6)
    for name, g, w in zip(NAMES[per_channel(layout)], got, want):
        assert g.shape == w.shape and worst(g, w) <= TOL, (name, worst(g, w))


@SMALL_LAYOUTS
def test_the_kernel_mixer_runs_a_device_on_its_rows_of_the_batch(layout, monkeypatch):
    """Under `sharding` the whole rule sits in one manual region over the
    batch; the weights' gradients (the taps', the scale's, `dt_bias`'s and
    `A_log`'s) are summed over the devices."""
    monkeypatch.setattr(L, "_TOKENS", 128)
    ops = mixer_operands(layout, 128, seed=2)
    weights = (0, 1, 2, 4, 5) if per_channel(layout) else (0, 1, 2)
    sharding = KernelSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        sharded = kernel_mixer(layout, sharding=sharding)
        got = jax.jit(lambda *a: sharded(*a) + jax.grad(mixer_objective(sharded), weights)(*a))(*ops)
        alone = kernel_mixer(layout)
        want = alone(*ops) + jax.grad(mixer_objective(alone), weights)(*ops)
    for name, g, w in zip(["out", "states"] + [NAMES[per_channel(layout)][2 + i] for i in weights], got, want):
        # (a sum over two devices' halves is float32's in another order: 1.6e-7 of `A_log`'s gradient of 400)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-6, rtol=1e-6, err_msg=name)

"""The chunked gated delta rule and the causal convolution (ops/linear_attention.py) against the recurrence run
token by token and against shifted adds, and the rule's two Pallas kernels interpreted against both
(operands, oracles and tolerances: tests/ops/linear_attention_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import linear_attention as L
from galvatron_tpu.ops.attention import KernelSharding
from tests.ops.linear_attention_cases import (B, DK, DV, HV, KERNEL, TOL, kernel_rule, objective, operands,
                                              recurrence, worst)


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


@pytest.mark.parametrize("chunks", [2, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_are_the_recurrence_and_the_xla_form(dtype, chunks, monkeypatch):
    """o, the final states and all five gradients, batch 2. Two tiles a grid
    step (one in float32): 5 chunks are 3 tiles behind a chunk of zeros, so
    the last step's block is not whole, and 2 chunks are one tile, less than a
    block."""
    monkeypatch.setattr(L, "_BLOCK", 2)
    ops = operands(64 * chunks, seed=chunks, **KERNEL)
    cast = tuple(x.astype(dtype) for x in ops[:3]) + ops[3:]
    exact = tuple(x.astype(jnp.float32) for x in cast)

    def objective(rule):  # the final states' gradient enters too
        def of(*a):
            o, states = rule(*a)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(jnp.cos(states))
        return of

    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = kernel_rule(*cast) + jax.grad(objective(kernel_rule), range(5))(*cast)
        xla = (L.gated_delta_rule(*cast, impl="xla")
               + jax.grad(objective(lambda *a: L.gated_delta_rule(*a, impl="xla")), range(5))(*cast))
        want = recurrence(*exact) + jax.grad(objective(recurrence), range(5))(*exact)
    assert got[0].dtype == dtype and got[0].shape == want[0].shape
    assert got[1].dtype == jnp.float32 and got[1].shape == (B, 2, 128, 128)
    for name, g, x, w in zip("o states dq dk dv dg dbeta".split(), got, xla, want):
        limit = TOL if dtype == jnp.float32 else max(TOL, worst(x, w))
        assert worst(g, w) <= limit, (name, worst(g, w), worst(x, w))
        assert worst(g, x.astype(jnp.float32)) <= 2 * limit, name


def test_the_kernels_solve_a_run_of_one_repeated_key():
    """`test_equal_neighbouring_keys_do_not_break_the_solve`, through the
    kernels' elimination and merges on a tile of 128."""
    q, k, v, g, beta = operands(128, seed=9, **KERNEL)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-6), jnp.full_like(beta, 0.999)
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, _ = kernel_rule(q, k, v, g, beta)
        want, _ = recurrence(q, k, v, g, beta)
    assert worst(o, want) < 1e-4


def test_the_kernels_run_a_device_on_its_rows_of_the_batch():
    """Under `sharding` the kernels sit in a manual region over the batch:
    two devices, a row each, the same numbers as one device on both."""
    ops = operands(128, seed=3, **KERNEL)
    sharding = KernelSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kernel_rule(*a, sharding=sharding))(*ops)
        want_o, want_state = kernel_rule(*ops)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-6)


def test_off_a_tpu_the_choice_is_the_xla_form_and_it_is_counted():
    ops = operands(64, seed=1)  # heads of 16 x 8: no kernel could take them
    with forms.recording() as took:
        got = L.gated_delta_rule(*ops)
        assert took == {forms.DELTA_RULE: {"xla": 1}}
        wide = operands(64, seed=1, **KERNEL)  # the kernels' widths, but this is a CPU
        L.gated_delta_rule(*wide)
    assert took == {forms.DELTA_RULE: {"xla": 2}}
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(L.gated_delta_rule(*ops, impl="xla")[0]))


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

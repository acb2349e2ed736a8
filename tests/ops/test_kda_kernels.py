"""The per-channel rule's Pallas kernels (`kda_fwd`, `kda_bwd`), interpreted, against the recurrence and the XLA
form (operands, oracles and tolerances: tests/ops/kda_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.ops import linear_attention as LA
from galvatron_tpu.ops.attention import KernelSharding
from tests.ops.kda_cases import KERNEL, in_float64, kernel_rule, operands, recurrence, worst, xla_rule


KERNEL_TOL = 1e-5


LEAVES = "o states dq dk dv dg dbeta".split()


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

"""The routed-experts block (ops/moe.py) against a per-token loop, and how its rows enter the grouped matmul
(operands and the loop: tests/ops/moe_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import moe
from tests.ops.moe_cases import (EXPERTS, HELD, KS, ROUTERS, TOKENS, _block, _choices, _loop, _objective, _operands)


@pytest.mark.parametrize("held", [None, HELD], ids=["all_held", "a_share"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("k", KS)
def test_block_equals_a_per_token_loop(k, router, held):
    kw = ROUTERS[router]
    ops = _operands(100 * k + len(router), held)
    choices = _choices(ops, k, kw["score"])
    if held is not None:  # the case means something: some rows held, some not
        inside = (choices >= held[0]) & (choices < held[0] + held[1])
        assert 0 < inside.sum() < inside.size

    def ours(y, router_kernel, wi, wo, bias):
        out, aux = _block(y, router_kernel, wi, wo, bias, k, kw, held)
        return _objective(out, aux, ops["cot"], kw["score"]), (out, aux)

    def plain(y, router_kernel, wi, wo, bias):
        out, aux = _loop(y, router_kernel, wi, wo, choices, held=held, **kw)
        return _objective(out, aux, ops["cot"], kw["score"]), (out, aux)

    args = tuple(ops[n] for n in ("y", "router", "wi", "wo", "bias"))
    (_, (out, aux)), grads = jax.value_and_grad(ours, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    (_, (want, want_aux)), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)

    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    for name, got, ref in zip(("y", "router", "wi", "wo", "bias"), grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6, err_msg="gradient of " + name)
    assert not np.any(np.asarray(grads[4]))  # no gradient moves the bias
    assert set(aux) == set(moe.moe_aux_names(kw["score"], kw["score"] == "sigmoid", held is not None))
    for name, ref in want_aux.items():
        np.testing.assert_allclose(aux[name], ref, rtol=1e-6, err_msg=name)


@pytest.mark.parametrize("k", KS)
def test_rows_enter_the_grouped_matmul_by_expert_then_by_token(k, monkeypatch):
    """What `gmm` / `tgmm` see: expert by expert, and within an expert token
    by token, each group as long as the expert's count."""
    kw = ROUTERS["sigmoid"]
    ops = _operands(7 + k, None)
    choices = _choices(ops, k, "sigmoid")
    seen = []
    committed = moe.grouped_matmul

    def recording(rows, kernels, group_sizes, *a, **kwargs):
        seen.append((np.asarray(rows), np.asarray(group_sizes)))
        return committed(rows, kernels, group_sizes, *a, **kwargs)

    monkeypatch.setattr(moe, "grouped_matmul", recording)
    _block(*(ops[n] for n in ("y", "router", "wi", "wo", "bias")), k, kw, None)

    pairs = sorted((e, t) for t in range(TOKENS) for e in choices[t])
    rows, group_sizes = seen[0]
    np.testing.assert_array_equal(rows, np.asarray(ops["y"])[[t for _, t in pairs]])
    np.testing.assert_array_equal(group_sizes, np.bincount([e for e, _ in pairs], minlength=EXPERTS))
    np.testing.assert_array_equal(seen[1][1], group_sizes)


@pytest.mark.parametrize("k", KS)
def test_a_share_sends_no_gradient_through_rows_it_does_not_hold(k):
    """A token none of whose experts is held gets a zero row back and sends
    exactly nothing to any operand; a token with a held expert does."""
    kw = ROUTERS["sigmoid"]
    held = (5, 3)  # few enough that at k = 8 some token still has none of them
    ops = _operands(31 + k, held)
    choices = _choices(ops, k, "sigmoid")
    holds = ((choices >= held[0]) & (choices < held[0] + held[1])).any(axis=1)
    assert holds.any() and not holds.all()

    def through(token_mask):
        def f(y, router_kernel, wi, wo):
            out, _ = _block(y, router_kernel, wi, wo, ops["bias"], k, kw, held)
            return jnp.sum(out * ops["cot"] * token_mask[:, None])
        return jax.grad(f, argnums=(0, 1, 2, 3))(*(ops[n] for n in ("y", "router", "wi", "wo")))

    out, _ = _block(*(ops[n] for n in ("y", "router", "wi", "wo", "bias")), k, kw, held)
    assert not np.any(np.asarray(out)[~holds]) and np.all(np.any(np.asarray(out)[holds] != 0, axis=1))
    d_y = np.asarray(through(jnp.ones((TOKENS,)))[0])
    assert not np.any(d_y[~holds]) and np.all(np.any(d_y[holds] != 0, axis=1))
    for name, g in zip(("y", "router", "wi", "wo"), through(jnp.asarray(~holds, jnp.float32))):
        assert not np.any(np.asarray(g)), name


def test_a_shape_the_row_movers_refuse_takes_the_xla_form():
    """What `_local_moe` can observe decides: off a TPU, at float32 rows, at a
    hidden size that is no whole 128-lane rows of words or under the floor, at
    a length that is not whole grid steps, the block is the XLA form, and
    equals the per-token loop as ever. Every width from the floor to the bound
    that is whole word rows is the movers' (PR 63: Kimi-Linear's 2304 among
    them; a packed row need not be whole tiles)."""
    bf16 = jnp.bfloat16
    assert moe.rows_form(True, bf16, 2048, 8192, 10) == "kernel"
    for hidden in (2304, 2560, 3072, 3584, 4096):
        assert moe.rows_form(True, bf16, hidden, 8192, 8) == "kernel", hidden
    for refused in ((False, bf16, 2048, 8192, 10), (True, jnp.float32, 2048, 8192, 10),
                    (False, bf16, 2304, 8192, 8), (True, jnp.float32, 2304, 8192, 8),
                    (True, bf16, 1920, 8192, 10), (True, bf16, 1024, 8192, 10), (True, bf16, 2304 + 128, 8192, 8),
                    (True, bf16, 2048, 8192 + 64, 10), (True, bf16, 2048, 96, 2),
                    # more than the kernels hold (tests/ops/test_tpu_compile_routed.py compiles AT the bounds)
                    (True, bf16, 4096, 32768, 8), (True, bf16, 8192, 8192, 8)):
        assert moe.rows_form(*refused) == "xla", refused
    with forms.recording() as took:
        test_block_equals_a_per_token_loop(4, "softmax", None)
    assert took[forms.MOE_ROWS]["xla"] > 0 and not took[forms.MOE_ROWS]["kernel"]


@pytest.mark.parametrize("n", [1, 7, 4096])
def test_a_permutation_by_sort_is_the_gather(n):
    """`_permuted(values, inverse)` is `values[index]`, to the bit: the block's
    three permutations of k x tokens scalars are sorts by the inverse (PR 40's
    second mechanism), in the XLA form as in the kernels'."""
    index = jax.random.permutation(jax.random.PRNGKey(n), n).astype(jnp.int32)
    inverse = jnp.zeros_like(index).at[index].set(jnp.arange(n, dtype=jnp.int32))
    for values in (jax.random.normal(jax.random.PRNGKey(n + 1), (n,), jnp.float32),
                   jnp.arange(n, dtype=jnp.int32)[::-1]):
        got = moe._permuted(values, inverse)
        assert got.dtype == values.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(values[index]))
    np.testing.assert_array_equal(  # its own inverse: what `_local_moe` derives `inv_order` with
        np.asarray(moe._permuted(jnp.arange(n, dtype=jnp.int32), index)), np.asarray(inverse))

"""The routed block's Pallas row movers (PR 40: `moe_rows_pack` / `moe_rows_back` / `moe_rows_out`), interpreted,
against the XLA gathers to the bit (operands: tests/ops/moe_cases.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import moe
from tests.ops.moe_cases import EXPERTS, HELD, KS, ROUTERS, WIDTH


# ------------------------------------------------- the row movers (PR 40)
# 2048: a bf16 row packs into ONE (8, 128) tile of words, the least the movers take; 2304 (Kimi-Linear's, PR 63):
# 9 sublane rows of words, so all rows but one in eight straddle a tile's edge
MOVER_CASES = ([(2048, k, router, held) for k in KS for router in sorted(ROUTERS) for held in (None, HELD)]
               + [(2304, 8, "sigmoid", HELD), (2304, 8, "softmax", None), (2304, 2, "softmax", HELD),
                  (2304, 4, "sigmoid", None)])


def _grid(x, step, most):
    """x rounded to multiples of `step` within +-`most`."""
    return jnp.clip(jnp.round(x / step), -most / step, most / step) * step


def _recorded_block(hidden, k, router, held, tokens, monkeypatch):
    """The block's own operands of `_dispatch` and `_combine` at bf16 rows of
    `hidden`: (y, order, inv_order) and (out, weights, order, inv_order), as a
    run of the XLA form hands them over."""
    kw = ROUTERS[router]
    keys = jax.random.split(jax.random.PRNGKey(1000 * k + len(router)), 5)
    n = EXPERTS if held is None else held[1]
    y = jax.random.normal(keys[0], (1, tokens, hidden), jnp.float32).astype(jnp.bfloat16)
    operands = (y, jax.random.normal(keys[1], (hidden, EXPERTS), jnp.float32) * 0.02,
                jax.random.normal(keys[2], (n, hidden, 2 * WIDTH), jnp.float32) * 0.02,
                jax.random.normal(keys[3], (n, WIDTH, hidden), jnp.float32) * 0.2)
    seen = {}
    for name in ("_dispatch", "_combine"):
        def recording(form, *args, name=name, committed=getattr(moe, name)):
            seen[name] = args
            return committed(form, *args)
        monkeypatch.setattr(moe, name, recording)
    with jax.disable_jit():  # concrete operands, not tracers
        moe.moe_ffn(*operands, experts_per_token=k, dtype=jnp.bfloat16, held=held,
                    bias=jax.random.normal(keys[4], (EXPERTS,), jnp.float32) * 0.05
                    if kw["score"] == "sigmoid" else None, **kw)
    monkeypatch.undo()
    return seen["_dispatch"], seen["_combine"]


@functools.partial(jax.jit, static_argnums=0)  # one trace a form and k: the cases of a k share shapes
def _both_ways(form, y, order, inv_order, out, weights, g_rows, g_tokens):
    rows, back = jax.vjp(lambda y: moe._dispatch(form, y, order, inv_order), y)
    summed, combine_back = jax.vjp(lambda o, w: moe._combine(form, o, w, order, inv_order), out, weights)
    return (rows, back(g_rows)[0], summed) + combine_back(g_tokens)


@pytest.mark.parametrize("hidden, k, router, held", MOVER_CASES,
                         ids=["%d-%d-%s-%s" % (h, k, r, "all_held" if held is None else "a_share")
                              for h, k, r, held in MOVER_CASES])
def test_the_row_movers_equal_the_xla_forms_to_the_bit(hidden, k, router, held, monkeypatch):
    """`moe_rows_pack`, `moe_rows_back` and `moe_rows_out`, interpreted on the
    CPU, against the XLA forms they stand in for on a TPU: `_dispatch`'s rows
    and `dy`, `_combine`'s output, `d_out` and `d_w`, on the block's own
    routing and weights. A permutation, float32 sums over k in one order and
    one rounding leave no room for a tolerance. Two things no form fixes are
    kept from showing: the order of a row's `hidden` products in `sum(out x g)`,
    and whether a compiler rounds a multiply and the add after it once or
    twice (the CPU's contracts them inside the interpreted kernel and not in
    the XLA form). So the COMBINE's operands lie on binary grids coarse enough
    that each of its float32 products and sums is exact; the dispatch's
    cotangent, which is only ever added, is any bf16, so the order of j shows
    there."""
    tokens = {1: 128, 2: 64, 4: 32, 6: 64, 8: 16}[k]  # the fewest that are whole grid steps of assignments
    (y, order, inv_order), (out, weights, _, _) = _recorded_block(hidden, k, router, held, tokens, monkeypatch)
    if held is not None:  # rows of experts held elsewhere come back zero and are moved all the same
        empty = ~np.any(np.asarray(out, np.float32), axis=1)
        assert 0 < empty.sum() < empty.size
    keys = jax.random.split(jax.random.PRNGKey(k), 2)
    out = _grid(out.astype(jnp.float32) * 64, 0.125, 4).astype(jnp.bfloat16)
    weights = jnp.maximum(_grid(weights, 2.0 ** -8, 2), 2.0 ** -8)
    g_tokens = _grid(jax.random.normal(keys[0], y.shape, jnp.float32), 0.125, 4).astype(jnp.bfloat16)
    g_rows = jax.random.normal(keys[1], out.shape, jnp.float32).astype(jnp.bfloat16)

    operands = (y, order, inv_order, out, weights, g_rows, g_tokens)
    want = _both_ways("xla", *operands)
    monkeypatch.setattr(moe, "ROWS_BACK_TILE", 16)
    monkeypatch.setattr(moe, "ROWS_OUT_TILE", 128)
    monkeypatch.setattr(moe, "PACK_TILE", 16)
    with pltpu.force_tpu_interpret_mode():
        got = _both_ways("kernel", *operands)
    for name, a, b in zip(("rows", "dy", "combined", "d_out", "d_w"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)
    assert np.any(np.asarray(got[4])) and np.any(np.asarray(got[2], np.float32))

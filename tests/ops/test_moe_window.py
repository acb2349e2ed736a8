"""A share's experts on a window of the sorted assignments (PR 47: `ops/moe._windowed_block`) against the whole
range to the bit, alone and on a mesh (operands: tests/ops/moe_cases.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import moe
from tests.ops.moe_cases import HIDDEN, ROUTERS, WIDTH, _objective


# ------------------------------------------------------- a share's window
# 64 tokens x 2 choices over 8 experts of which 2 are held, at a row tile of
# 8: the even share is 32 rows and the window (1.5 x 32 in whole tiles) + a
# tile = 56 of the 128. name: (the first held expert, assignments an expert,
# whether the block must take the whole range)
W_TOKENS, W_K, W_EXPERTS, W_HELD, W_TILE, W_ROWS = 64, 2, 8, 2, 8, 56


WINDOW_CASES = {
    "at_the_start": (0, (20, 20, 11, 13, 17, 19, 14, 14), False),
    "at_the_start_exactly_the_window": (0, (30, 26, 9, 11, 12, 13, 14, 13), False),
    "at_the_start_one_row_over": (0, (30, 27, 9, 11, 12, 13, 13, 13), True),
    # the range starts at row 27, the window at 24 and ends at 80
    "in_the_middle": (3, (9, 9, 9, 14, 16, 23, 24, 24), False),
    "in_the_middle_empty": (3, (9, 9, 9, 0, 0, 33, 34, 34), False),
    "in_the_middle_exactly_the_window": (3, (9, 9, 9, 27, 26, 16, 16, 16), False),
    "in_the_middle_one_row_over": (3, (9, 9, 9, 27, 27, 16, 16, 15), True),
    # a tile of its own: the window starts where the range does
    "on_a_tile": (2, (16, 16, 21, 19, 14, 14, 14, 14), False),
    # the window cannot start at the tile below the range: it ends with the rows
    "at_the_end": (6, (16, 16, 17, 17, 16, 16, 12, 18), False),
    "at_the_end_longer_than_the_window": (6, (12, 12, 12, 12, 10, 10, 30, 30), True),
    "all_rows_held": (6, (0, 0, 0, 0, 0, 0, 64, 64), True),
    "one_expert_of_the_two": (3, (9, 9, 9, 0, 41, 20, 20, 20), False),
}


def _steered(case, seed):
    """Operands whose router sends exactly `counts[e]` assignments to expert
    e: the sorted list of experts, each as often as it is chosen, is dealt to
    the tokens twice over, so a token's two experts differ."""
    first, counts, _ = WINDOW_CASES[case]
    assert sum(counts) == W_TOKENS * W_K and max(counts) <= W_TOKENS
    dealt = np.repeat(np.arange(W_EXPERTS), counts).reshape(W_K, W_TOKENS).T  # (tokens, k)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    pull = np.zeros((W_TOKENS, HIDDEN), np.float32)
    for j in range(W_K):
        pull[np.arange(W_TOKENS), dealt[:, j]] = 3.0 - j
    return dict(
        y=jnp.asarray(pull) + 0.1 * jax.random.normal(keys[0], (W_TOKENS, HIDDEN), jnp.float32),
        router=jnp.eye(HIDDEN, W_EXPERTS) + 0.02 * jax.random.normal(keys[1], (HIDDEN, W_EXPERTS), jnp.float32),
        wi=jax.random.normal(keys[2], (W_HELD, HIDDEN, 2 * WIDTH), jnp.float32) * 0.2,
        wo=jax.random.normal(keys[3], (W_HELD, WIDTH, HIDDEN), jnp.float32) * 0.2,
        bias=jax.random.normal(keys[4], (W_EXPERTS,), jnp.float32) * 0.05,
        cot=jax.random.normal(keys[5], (W_TOKENS, HIDDEN), jnp.float32),
    )


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("case", sorted(WINDOW_CASES))
def test_the_window_equals_the_whole_range_to_the_bit(case, router, monkeypatch):
    """A share's block with the experts over the window of the sorted
    assignments and over all of them: the same output and the same gradient
    to y, the router and both kernels, bit for bit, wherever the held range
    lies; a range that outgrows the window takes the whole-range branch, to
    the same bits again, and `window_fallbacks` counts such blocks and no
    other."""
    first, counts, falls = WINDOW_CASES[case]
    held, kw = (first, W_HELD), ROUTERS[router]
    ops = _steered(case, 7 * len(case) + len(router))
    monkeypatch.setattr(moe, "GMM_TILING", (W_TILE,) + moe.GMM_TILING[1:])
    assert moe.window_rows(W_TOKENS * W_K, W_EXPERTS, held) == W_ROWS

    def objective(y, router_kernel, wi, wo):
        out, aux = moe.moe_ffn(y[None], router_kernel, wi, wo, experts_per_token=W_K, dtype=jnp.float32,
                               bias=ops["bias"] if kw["score"] == "sigmoid" else None, held=held, **kw)
        return _objective(out[0], aux, ops["cot"], kw["score"]), (out[0], aux)

    def both_directions():
        args = tuple(ops[n] for n in ("y", "router", "wi", "wo"))
        (_, (out, aux)), grads = jax.jit(jax.value_and_grad(
            objective, argnums=(0, 1, 2, 3), has_aux=True))(*args)
        return out, aux, grads

    with forms.recording() as took:
        out, aux, grads = both_directions()
    assert took[forms.EXPERT_WINDOW] == {str(W_ROWS): 1}
    assert float(aux["rows_held"]) == sum(counts[first:first + W_HELD])  # the router went where it was steered
    assert float(aux["window_fallbacks"]) == falls

    monkeypatch.setattr(moe, "WINDOW_OVER_EVEN", float(W_EXPERTS))  # no shorter than the range: none is built
    assert moe.window_rows(W_TOKENS * W_K, W_EXPERTS, held) == 0
    want, want_aux, want_grads = both_directions()
    assert float(want_aux["window_fallbacks"]) == 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for name, got, ref in zip(("y", "router", "wi", "wo"), grads, want_grads):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref), err_msg="gradient of " + name)
    if any(counts[first:first + W_HELD]):
        assert np.any(np.asarray(out)) and all(np.any(np.asarray(g)) for g in grads)


def test_the_window_on_a_mesh_is_each_devices_own(monkeypatch):
    """On a mesh the block runs in a manual region on the batch rows a device
    holds (`moe_ffn`'s `shard_map`): each device places a window over its own
    sorted assignments, half as long as one device's over all of them, and
    output, gradients and counters are one device's."""
    from jax.sharding import Mesh

    from galvatron_tpu.ops.attention import KernelSharding

    monkeypatch.setattr(moe, "GMM_TILING", (W_TILE,) + moe.GMM_TILING[1:])
    ops = _steered("in_the_middle", 3)
    held, kw = (3, W_HELD), ROUTERS["softmax"]

    def objective(sharding, y, router_kernel, wi, wo):
        out, aux = moe.moe_ffn(y.reshape(2, W_TOKENS // 2, HIDDEN), router_kernel, wi, wo, experts_per_token=W_K,
                               dtype=jnp.float32, held=held, sharding=sharding, **kw)
        return _objective(out.reshape(W_TOKENS, HIDDEN), aux, ops["cot"], "softmax"), aux

    args = tuple(ops[n] for n in ("y", "router", "wi", "wo"))
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    with forms.recording() as took:
        (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
            functools.partial(objective, None), argnums=(0, 1, 2, 3), has_aux=True))(*args)
        (got, aux), grads = jax.jit(jax.value_and_grad(
            functools.partial(objective, KernelSharding(mesh, batch_axes=("dp",))), argnums=(0, 1, 2, 3), has_aux=True))(*args)
    assert took[forms.EXPERT_WINDOW] == {str(W_ROWS): 1, str(moe.window_rows(W_TOKENS * W_K // 2, W_EXPERTS, held)): 1}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in ("rows_held", "window_fallbacks", "load_balance"):
        np.testing.assert_allclose(aux[name], want_aux[name], rtol=1e-6, err_msg=name)
    for name, g, ref in zip(("y", "router", "wi", "wo"), grads, want_grads):
        np.testing.assert_allclose(g, ref, rtol=2e-5, atol=2e-6, err_msg="gradient of " + name)

"""ops/moe.py against a plain per-token loop, float32 on the CPU.

The loop below sorts nothing and gathers nothing: for every token it walks
the token's `k` chosen experts, multiplies the token's row by that expert's
two kernels and adds the result at the router's weight. The block must give
the same output, the same gradient to every operand and the same counters,
whatever `k` is (a TPU tiles an array's two minor dimensions by 8 x 128, so
the block keeps its `tokens x k` assignments k-major: `ops/moe.py`).
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.ops import moe

TOKENS, HIDDEN, WIDTH, EXPERTS = 24, 16, 8, 16
HELD = (5, 6)  # experts 5 to 10 of the 16
KS = (1, 2, 4, 6, 8)
ROUTERS = {
    "softmax": dict(score="softmax", norm_topk_prob=False, scale=1.0),
    "sigmoid": dict(score="sigmoid", norm_topk_prob=True, scale=1.8),
}


def _operands(seed, held):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = EXPERTS if held is None else held[1]
    return dict(
        y=jax.random.normal(keys[0], (TOKENS, HIDDEN), jnp.float32),
        router=jax.random.normal(keys[1], (HIDDEN, EXPERTS), jnp.float32) * 0.3,
        wi=jax.random.normal(keys[2], (n, HIDDEN, 2 * WIDTH), jnp.float32) * 0.2,
        wo=jax.random.normal(keys[3], (n, WIDTH, HIDDEN), jnp.float32) * 0.2,
        bias=jax.random.normal(keys[4], (EXPERTS,), jnp.float32) * 0.05,
        cot=jax.random.normal(keys[5], (TOKENS, HIDDEN), jnp.float32),
    )


def _scores(y, router, score):
    logits = jnp.dot(y, router, precision=jax.lax.Precision.HIGHEST)
    return logits, (jax.nn.softmax(logits, axis=-1) if score == "softmax" else jax.nn.sigmoid(logits))


def _choices(ops, k, score):
    """(tokens, k) numpy: the k highest experts a token, ties to the lower
    index, by the score (plus the bias for the sigmoid router)."""
    ranked = np.asarray(_scores(ops["y"], ops["router"], score)[1])
    if score == "sigmoid":
        ranked = ranked + np.asarray(ops["bias"])
    return np.argsort(-ranked, axis=-1, kind="stable")[:, :k]


def _loop(y, router, wi, wo, choices, *, score, norm_topk_prob, scale, held):
    """The block as a loop over tokens and their choices; `choices` concrete."""
    first, count = (0, EXPERTS) if held is None else held
    logits, scores = _scores(y, router, score)
    out = []
    for t in range(TOKENS):
        w = scores[t, choices[t]]
        if norm_topk_prob:
            w = w / (jnp.sum(w) + (1e-20 if score == "sigmoid" else 0.0))
        w = w * scale
        row = jnp.zeros((HIDDEN,), jnp.float32)
        for j, e in enumerate(choices[t] - first):
            if 0 <= e < count:
                mid = y[t] @ wi[e]
                row = row + w[j] * ((jax.nn.silu(mid[:WIDTH]) * mid[WIDTH:]) @ wo[e])
        out.append(row)
    counts = np.bincount(choices.reshape(-1), minlength=EXPERTS).astype(np.float32)
    aux = {"load_max_over_mean": counts.max() / counts.mean()}
    if score == "softmax":
        aux["load_balance"] = EXPERTS * jnp.sum(counts / TOKENS * jnp.mean(scores, axis=0))
        aux["router_z"] = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    else:
        aux["counts"] = counts
    if held is not None:
        aux["rows_held"] = counts[first:first + count].sum()
    return jnp.stack(out), aux


def _block(y, router, wi, wo, bias, k, router_kw, held):
    out, aux = moe.moe_ffn(y[None], router, wi, wo, experts_per_token=k, dtype=jnp.float32,
                           bias=bias if router_kw["score"] == "sigmoid" else None, held=held,
                           **router_kw)
    return out[0], aux


def _objective(out, aux, cot, score):
    return jnp.sum(out * cot) + (aux["load_balance"] + aux["router_z"] if score == "softmax" else 0.0)


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


# ------------------------------------------------- the row movers (PR 40)
MOVER_HIDDEN = 2048  # the least a bf16 row packs into whole (8, 128) tiles of words at


def _grid(x, step, most):
    """x rounded to multiples of `step` within +-`most`."""
    return jnp.clip(jnp.round(x / step), -most / step, most / step) * step


def _recorded_block(k, router, held, tokens, monkeypatch):
    """The block's own operands of `_dispatch` and `_combine` at bf16 rows of
    2048: (y, order, inv_order) and (out, weights, order, inv_order), as a run
    of the XLA form hands them over."""
    kw = ROUTERS[router]
    keys = jax.random.split(jax.random.PRNGKey(1000 * k + len(router)), 5)
    n = EXPERTS if held is None else held[1]
    y = jax.random.normal(keys[0], (1, tokens, MOVER_HIDDEN), jnp.float32).astype(jnp.bfloat16)
    operands = (y, jax.random.normal(keys[1], (MOVER_HIDDEN, EXPERTS), jnp.float32) * 0.02,
                jax.random.normal(keys[2], (n, MOVER_HIDDEN, 2 * WIDTH), jnp.float32) * 0.02,
                jax.random.normal(keys[3], (n, WIDTH, MOVER_HIDDEN), jnp.float32) * 0.2)
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


@pytest.mark.parametrize("held", [None, HELD], ids=["all_held", "a_share"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("k", KS)
def test_the_row_movers_equal_the_xla_forms_to_the_bit(k, router, held, monkeypatch):
    """`moe_rows_pack`, `moe_rows_back` and `moe_rows_out`, interpreted on the
    CPU, against the XLA forms they stand in for on a TPU: `_dispatch`'s rows
    and `dy`, `_combine`'s output, `d_out` and `d_w`, on the block's own
    routing and weights. A permutation, float32 sums over k in one order and
    one rounding leave no room for a tolerance. Two things no form fixes are
    kept from showing: the order of a row's 2048 products in `sum(out x g)`,
    and whether a compiler rounds a multiply and the add after it once or
    twice (the CPU's contracts them inside the interpreted kernel and not in
    the XLA form). So the COMBINE's operands lie on binary grids coarse enough
    that each of its float32 products and sums is exact; the dispatch's
    cotangent, which is only ever added, is any bf16, so the order of j shows
    there."""
    tokens = {1: 128, 2: 64, 4: 32, 6: 64, 8: 16}[k]  # the fewest that are whole grid steps of assignments
    (y, order, inv_order), (out, weights, _, _) = _recorded_block(k, router, held, tokens, monkeypatch)
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


def test_a_shape_the_row_movers_refuse_takes_the_xla_form():
    """What `_local_moe` can observe decides: off a TPU, at float32 rows, at a
    hidden size that does not pack into whole tiles, at a length that is not
    whole grid steps, the block is the XLA form, and equals the per-token
    loop as ever."""
    bf16 = jnp.bfloat16
    assert moe.rows_form(True, bf16, 2048, 8192, 10) == "kernel"
    for refused in ((False, bf16, 2048, 8192, 10), (True, jnp.float32, 2048, 8192, 10),
                    (True, bf16, 1920, 8192, 10), (True, bf16, 1024, 8192, 10),
                    (True, bf16, 2048, 8192 + 64, 10), (True, bf16, 2048, 96, 2),
                    # more than the kernels hold (tests/ops/test_tpu_compile.py compiles AT the bounds)
                    (True, bf16, 4096, 32768, 8), (True, bf16, 8192, 8192, 8)):
        assert moe.rows_form(*refused) == "xla", refused
    before = dict(moe.ROWS_TOOK)
    test_block_equals_a_per_token_loop(4, "softmax", None)
    assert moe.ROWS_TOOK["xla"] > before.get("xla", 0)
    assert moe.ROWS_TOOK["kernel"] == before.get("kernel", 0)


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

    before = moe.WINDOWS_TOOK[W_ROWS]
    out, aux, grads = both_directions()
    assert moe.WINDOWS_TOOK[W_ROWS] == before + 1
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
    before = collections.Counter(moe.WINDOWS_TOOK)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        functools.partial(objective, None), argnums=(0, 1, 2, 3), has_aux=True))(*args)
    (got, aux), grads = jax.jit(jax.value_and_grad(
        functools.partial(objective, KernelSharding(mesh, batch_axes=("dp",))), argnums=(0, 1, 2, 3), has_aux=True))(*args)
    assert moe.WINDOWS_TOOK - before == {W_ROWS: 1, moe.window_rows(W_TOKENS * W_K // 2, W_EXPERTS, held): 1}
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name in ("rows_held", "window_fallbacks", "load_balance"):
        np.testing.assert_allclose(aux[name], want_aux[name], rtol=1e-6, err_msg=name)
    for name, g, ref in zip(("y", "router", "wi", "wo"), grads, want_grads):
        np.testing.assert_allclose(g, ref, rtol=2e-5, atol=2e-6, err_msg="gradient of " + name)

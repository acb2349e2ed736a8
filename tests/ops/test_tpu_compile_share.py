"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
a held share of the experts at GLM-4.7-Flash's widths: the window's `cond`, k out of the tiles, the row movers' calls."""

import collections
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import MEGABLOX_CALL, v5e_2x2  # noqa: F401  (the fixture)


GLM_TOKENS, GLM_H, GLM_WIDTH, GLM_EXPERTS, GLM_HELD = 8192, 2048, 1536, 64, 8  # the cell glm47f-c1-s8k


def _held_share(v5e_2x2, k):
    """ops/moe.py at GLM-4.7-Flash's widths with 8 of the 64 experts held and
    `k` a token: the loss, its operands' shapes, forward + backward compiled."""
    from galvatron_tpu.ops.moe import moe_ffn

    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))

    def loss(y, router, bias, wi, wo):
        out, aux = moe_ffn(y, router, wi, wo, experts_per_token=k, norm_topk_prob=True,
                           dtype=y.dtype, sharding=on_chip, score="sigmoid", bias=bias,
                           scale=1.8, held=(16, GLM_HELD))
        return jnp.sum(out.astype(jnp.float32) ** 2), aux

    f32 = jnp.float32
    operands = (jax.ShapeDtypeStruct((1, GLM_TOKENS, GLM_H), jnp.bfloat16, sharding=one),
                jax.ShapeDtypeStruct((GLM_H, GLM_EXPERTS), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_EXPERTS,), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_HELD, GLM_H, 2 * GLM_WIDTH), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_HELD, GLM_WIDTH, GLM_H), f32, sharding=one))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4), has_aux=True)).lower(*operands).compile()
    return loss, operands, compiled.as_text()


@pytest.fixture(scope="module")
def held_share(v5e_2x2):
    return functools.cache(lambda k: _held_share(v5e_2x2, k))  # one compile a k


def _branches(text, index):
    """The instructions of branch `index` of every `conditional` in a compiled
    text: 1 is `jax.lax.cond`'s true branch (a share's window), 0 its false
    one (the whole range)."""
    computations, name = {}, None
    for line in text.splitlines():
        start = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if start:
            name = start.group(1)
        elif name is not None:
            computations.setdefault(name, []).append(line.strip())
    taken = [re.findall(r"%([\w.\-]+)", found)[index]
             for found in re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text)]
    return [line for name in taken for line in computations[name]]


def test_a_held_share_of_the_experts_compiles_for_v5e(held_share):
    """Forward and backward: the sigmoid router with its bias ranks all 64,
    the megablox kernels take the held groups' offset (`group_offset`) and the
    kernels of 8 experts, and the counters come back. Each direction is a
    `cond` of the window and the whole range, and the backward makes the
    experts' forward again: 2 + 6 calls a branch, and in the whole range's the
    up projection a third time, after the combine's backward."""
    loss, operands, text = held_share(4)
    assert "ragged-dot" not in text
    assert [len(re.findall(MEGABLOX_CALL, "\n".join(_branches(text, index)))) for index in (0, 1)] == [9, 8]
    aux = jax.eval_shape(loss, *operands)[1]
    assert set(aux) == {"load_max_over_mean", "counts", "bias_abs_max", "rows_held", "window_fallbacks"}
    assert aux["counts"].shape == (GLM_EXPERTS,)


@pytest.mark.parametrize("k", [4, 6, 8])
def test_the_routed_block_keeps_k_out_of_the_tiles_on_v5e(held_share, k):
    """A TPU tiles an array's two minor dimensions by 8 x 128, so a (tokens, k,
    hidden) array whose k is not a multiple of 8 is padded to one, every
    reshape to or from (tokens x k, hidden) moves every row, and the compiler
    stops fusing across it: float32 copies of all rows, a broadcast of the
    cotangent written out (PERF.md, PR 34). `ops/moe.py` keeps the assignments
    k-major and sums over k slab by slab, so between the gathers and the sums
    of dispatch and combine nothing of the kind is left, whatever k is. A
    reshape that survives to the compiled text is a physical one."""
    from galvatron_tpu.obs import tracing

    text = held_share(k)[2]
    every_row = GLM_TOKENS * k * GLM_H
    ops = [line for line in _branches(text, 1)  # the window's branch of both directions
           if re.search(r'op_name="[^"]*(%s|%s)' % (re.escape(tracing.MOE_COMBINE),
                                                     re.escape(tracing.MOE_DISPATCH)), line)]
    assert len(ops) > 10 and any(re.search(r"transpose\(.*%s" % re.escape(tracing.MOE_COMBINE), op) for op in ops)
    offenders = []
    for op in ops:
        name, result, kind = re.match(r"(\S+) = (.*?[})]) ([a-z\-]+)\(", op).groups()
        sizes = [(dtype, int(np.prod([int(d) for d in dims.split(",") if d])))
                 for dtype, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]*)\]", result)]
        if (kind == "reshape" or (kind == "broadcast" and every_row in [n for _, n in sizes])
                or ("f32", every_row) in sizes):
            offenders.append("%s = %s %s" % (name, result, kind))
    assert not offenders, "\n".join(offenders)


def _scope(op_name):
    """The innermost `gt.` scope of an op's name."""
    return re.findall(r"gt\.[a-z_.]+", op_name)[-1]


def test_the_routed_blocks_rows_move_by_dma_on_v5e(held_share):
    """PR 40: on a TPU, at bf16 rows of 2048 and whole grid steps, the sum
    over k of the combine's forward and of the dispatch's backward and the
    combine's backward are the row movers (`ops/moe.rows_form`): under
    `gt.moe.combine` and `gt.moe.dispatch` the step has their custom calls,
    each fed by a packing pass, and XLA gathers (k x tokens, hidden) rows in
    the dispatch's own forward alone, whose small source it keeps in fast
    memory (PERF.md, PR 40: the sweep); once a direction, since a share's
    backward makes its forward again (read off the window's branch of each
    `cond`: the whole range's is the same block)."""
    from galvatron_tpu.obs import tracing

    text = "\n".join(_branches(held_share(4)[2], 1))
    calls = dict.fromkeys(("moe_rows_pack", "moe_rows_back", "moe_rows_out"), ())
    for line in text.splitlines():
        found = re.search(r'custom_call_target="tpu_custom_call".*op_name="([^"]*)/(moe_rows_\w+)/pallas_call"', line)
        if found:
            calls[found.group(2)] += (found.group(1),)
    combine, dispatch = tracing.MOE_COMBINE, tracing.MOE_DISPATCH
    assert sorted(_scope(op) for op in calls["moe_rows_back"]) == [combine, dispatch], calls
    assert [_scope(op) for op in calls["moe_rows_out"]] == [combine], calls
    assert sorted(_scope(op) for op in calls["moe_rows_pack"]) == [combine, combine, dispatch], calls
    every_row = r"bf16\[%d,%d\]" % (4 * GLM_TOKENS, GLM_H)
    gathers = [line for line in text.splitlines()  # inside a branch a gather is a fusion of its own
               if re.search(r"= %s\S* (gather|fusion)\(" % every_row, line)
               and re.search(r'op_name="[^"]*(%s|%s)[^"]*/gather"' % (re.escape(combine), re.escape(dispatch)), line)]
    assert len(gathers) == 2 and all(re.search(r'%s/gather"' % re.escape(dispatch), line) for line in gathers), gathers


def test_a_shares_experts_work_on_a_window_of_the_rows_on_v5e(held_share, v5e_2x2):
    """PR 47: with a share of the experts held, everything under
    `gt.moe.experts` in the window's branch of both directions (the grouped
    matmuls, megablox's fill of the rows it skips, the activation and its
    backward) runs over `window_rows` rows: nothing there has an array as long
    as the `k x tokens` assignments but the rows it cuts its window from and
    the zeros it lays its result into. The whole-range branch beside it does,
    which is what the window is for. And the block's two rules are traced
    and lowered once a shape (`jax.jit`), whatever the number of layers:
    the forward rule's two kernels, the backward's six, for the window and
    for the whole range."""
    from galvatron_tpu.obs import tracing
    from galvatron_tpu.ops import moe

    loss, operands, text = held_share(4)
    every, window = 4 * GLM_TOKENS, moe.window_rows(4 * GLM_TOKENS, GLM_EXPERTS, (16, GLM_HELD))
    assert window == 6656  # 1.5 x 4096 in 512-row tiles, and a tile

    def long_ops(index):
        """(what made it, the rows of its result) of the experts' ops in a branch whose result is as long as the assignments"""
        found = []
        for line in _branches(text, index):
            name = re.search(r'op_name="([^"]*%s[^"]*)"' % re.escape(tracing.MOE_EXPERTS), line)
            result = re.match(r"(?:ROOT )?\S+ = (.*?) [a-z\-]+\(", line)
            if name and result and re.search(r"\[%d,\d+\]" % every, result.group(1)):
                found.append(name.group(1).rsplit("/", 1)[-1])
        return sorted(set(found))

    assert long_ops(1) == ["dynamic_update_slice"], long_ops(1)  # laid into zeros, in place
    assert {"select_n", "mul", "pallas_call"} <= set(long_ops(0)), long_ops(0)
    windowed = "\n".join(line for line in _branches(text, 1) if tracing.MOE_EXPERTS in line)
    assert re.search(r"\[%d,\d+\]\S* fusion\(.*select_n" % window, windowed)  # the fill, over the window
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4), has_aux=True)).lower(*operands).as_text()
    kernels = re.findall(r"func\.func private @(t?gmm)\w*\((.*?)\) ->", lowered)
    shapes = collections.Counter((name, tuple(re.findall(r"tensor<([\dx]+)x", operands_))) for name, operands_ in kernels)
    assert len(shapes) == 12 and len(kernels) == 16, shapes  # 2 + 6 a length; the forward's two are both rules'

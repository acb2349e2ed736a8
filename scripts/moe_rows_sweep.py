#!/usr/bin/env python3
"""Chip measurement behind the routed block's row movers (ops/moe.py:
`moe_rows_pack`, `moe_rows_back`, `moe_rows_out`) and behind which sites take
them, at the routed cells' shapes (8192 tokens of bf16 rows: 2048 wide at k =
10, 8 and 4, 81920, 65536 and 32768 assignments a block; 2304 wide, Kimi-
Linear's, at k = 8). Not a benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/moe_rows_sweep.py [back,out,pack ...] [hiddenxk ...]

(`2304x8`: that shape alone, and the blocks of that shape) and read
`chiprun_out/moe_rows_sweep.json`, written anew after every shape. A site at
a time, alone, four calls on four sets of operands in one program and the
time a call (so that a program's dispatch, a fifth of a millisecond and more,
weighs a quarter):

    sum_w     the combine's forward: rows in expert order -> token order,
              weighted and summed over k                      (site 1)
    sum       the dispatch's backward: the same without weights (site 1)
    back      the combine's backward: the token's cotangent to each of its
              assignments, `d_out` and the rows' `sum(out x g)` (site 2)
    gather    the dispatch's forward: `y[order % tokens]`      (site 3; its
              kernel form is this script's alone, `gather_by_dma`)

each in the XLA form and in the kernel form, for every setting of
(`ROWS_BACK_TILE`, `ROWS_OUT_TILE`, `PACK_TILE`) given, the committed one
first; `pack_all` / `pack_tokens` are the packing passes alone (they are IN
the kernel forms' times too) and `rows_back` / `rows_out` the two movers alone,
on rows packed beforehand. The copies in flight are a grid step's rows:
k x `ROWS_BACK_TILE` for `moe_rows_back`, `ROWS_OUT_TILE` for `moe_rows_out`.
Beside the times: the ns a row moved, the share of the bytes' floor (each
operand across HBM once at 819 GB/s, the rows' own bytes and no padding), and
how many elements of each result differ from the XLA form's (0: equal to the
bit). Then `widths`: every width `rows_form` lets through (2048 to 4096 in
steps of 256: a row of 8 to 16 sublane rows of words, whole tiles at the two
ends alone), a block of k = 2, the three sites against the XLA form. Then
`scalars`: a permutation of k x tokens float32 as a gather, as a scatter and
as the sort `_permuted` makes of it. Last `blocks`:
the whole routed block (`moe_ffn`, forward and the gradient of every operand)
at the cells' routers, widths and held shares, through the kernels
against the XLA form on the same operands: which results are equal to the
bit, and how far the others lie. Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOKENS, EXPERTS, CALLS = 8192, 64, 4
# (hidden, k): qwen3next-c1-s8k, olmoe-c1-s4k, glm47f-c1-s8k, kimilin-c1-s8k
SHAPES = ((2048, 10), (2048, 8), (2048, 4), (2304, 8))
# the cells' routed blocks: hidden, experts, held, k, width, the router
BLOCKS = {
    "qwen3next-c1-s8k": dict(hidden=2048, experts=512, held=(64, 32), k=10, width=512,
                             router=dict(score="softmax", norm_topk_prob=True)),
    "olmoe-c1-s4k": dict(hidden=2048, experts=64, held=None, k=8, width=1024,
                         router=dict(score="softmax", norm_topk_prob=False)),
    "glm47f-c1-s8k": dict(hidden=2048, experts=64, held=(16, 8), k=4, width=1536,
                          router=dict(score="sigmoid", norm_topk_prob=True, scale=1.8)),
    "kimilin-c1-s8k": dict(hidden=2304, experts=256, held=(0, 8), k=8, width=1024,
                           router=dict(score="sigmoid", norm_topk_prob=True, scale=2.446)),
}
HBM = 819e9


def timed(fn, *args, repeat=8):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(repeat):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out) * 1e3 / CALLS


def operand_set(M, hidden, k, seed):
    """One block's operands at random routing: rows in expert order, the
    tokens' rows, the router's weights, the order and its inverse, and the
    combine's backward's per-row forms of the last two."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    slot = jnp.arange(k * TOKENS, dtype=jnp.int32)
    rows = jax.random.normal(keys[0], (k * TOKENS, hidden), jnp.float32).astype(jnp.bfloat16)
    y = jax.random.normal(keys[1], (TOKENS, hidden), jnp.float32).astype(jnp.bfloat16)
    w = jax.random.uniform(keys[2], (TOKENS, k), jnp.float32)
    experts = jax.lax.top_k(jax.random.uniform(keys[3], (TOKENS, EXPERTS), jnp.float32), k)[1]
    order = jnp.argsort(M._k_major(experts) * TOKENS + slot % TOKENS).astype(jnp.int32)
    return dict(rows=rows, y=y, w=w, order=order, inv=jnp.zeros_like(order).at[order].set(slot),
                w_rows=w.T.reshape(-1)[order], token_of=order % TOKENS)


def save(out):
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_rows_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)


def gather_by_dma(M, y, token_of):
    """Site 3, the dispatch's forward `y[token_of]`, through the row mover
    with no arithmetic behind it: `ops/moe._rows_out` without its epilogue.
    Only this script runs it: the step keeps XLA's gather there (PERF.md, PR
    40), and this is what that answer was measured with."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hidden, count, lanes, group = y.shape[1], token_of.shape[0], 128, 16
    sub, half, tile = hidden // (2 * lanes), hidden // 2, M.ROWS_OUT_TILE

    def kernel(tok_ref, src_ref, rows_ref, buf, sem):
        fetch, wait = M._mover(tok_ref, src_ref, buf, sem, tile=tile, sub=sub)

        def work(slot, first):
            rows = pl.ds(first, group)
            for q in range(sub):
                lo, hi = M._halves(buf[slot, 0, pl.ds(first * sub + q, group, stride=sub), :])
                rows_ref[rows, q * lanes:(q + 1) * lanes] = lo.astype(y.dtype)
                rows_ref[rows, half + q * lanes:half + (q + 1) * lanes] = hi.astype(y.dtype)

        M._pipelined(fetch, wait, count // tile, tile, work)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((count, hidden), y.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(count // tile,), in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, hidden), lambda i, tok: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, 1, tile * sub, lanes), "uint32"), pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="moe_rows_gather")(token_of, M._pack_rows(y, M.PACK_TILE))


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("moe_rows_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from galvatron_tpu.ops import moe as M

    bf16 = jnp.bfloat16
    committed = (M.ROWS_BACK_TILE, M.ROWS_OUT_TILE, M.PACK_TILE)
    settings = [committed] + [tuple(int(x) for x in a.split(",")) for a in argv if "," in a]
    shapes = [tuple(int(x) for x in a.split("x")) for a in argv if "x" in a] or list(SHAPES)
    out = {"device": jax.devices()[0].device_kind, "tokens": TOKENS, "shapes": []}

    def each(fn):
        """One program: `fn` on each of the CALLS sets of operands (a set's
        arrays are arguments of their own: a slice of a stack would be copied
        out before a kernel reads it, 335 MB of it)."""
        return jax.jit(lambda *sets: [fn(*one) for one in sets])

    def sites(form):
        return {
            "sum_w": each(lambda rows, w, order, inv: M._sum_over_k(form, rows, inv, TOKENS, w)),
            "sum": each(lambda rows, w, order, inv: M._sum_over_k(form, rows, inv, TOKENS, None)),
            "back": each(lambda rows, w, order, inv, g: M._combine_bwd(form, (rows, w, order, inv), g)[:2]),
            "gather": each(
                (lambda y, order: gather_by_dma(M, y, order % TOKENS))
                if form == "kernel" else (lambda y, order: y[order % TOKENS])),
        }

    for hidden, k in shapes:
        sets = [operand_set(M, hidden, k, 10 * k + c) for c in range(CALLS)]
        pick = lambda *names: [tuple(one[n] for n in names) for one in sets]
        operands = {"sum_w": pick("rows", "w", "order", "inv"), "sum": pick("rows", "w", "order", "inv"),
                    "back": pick("rows", "w", "order", "inv", "y"), "gather": pick("y", "order"),
                    "pack_all": pick("rows"), "pack_tokens": pick("y")}
        every, some = k * TOKENS * hidden * 2, TOKENS * hidden * 2  # bytes of all rows, of the tokens'
        # each operand across HBM once, the gathered rows not written back, no padding counted
        floor_ms = {name: nbytes / HBM * 1e3 for name, nbytes in {
            "sum_w": every + some, "sum": every + some, "back": 3 * every, "gather": 2 * every,
            "pack_all": 2 * every, "pack_tokens": 2 * some,
            "rows_back_w": every + some, "rows_back": every + some, "rows_out": 3 * every}.items()}
        shape = {"hidden": hidden, "k": k, "rows": k * TOKENS, "settings": [], "hbm_floor_ms": floor_ms}
        xla = sites("xla")
        want = {name: xla[name](*operands[name]) for name in xla}
        shape["xla_ms"] = {name: timed(xla[name], *operands[name]) for name in xla}
        print("hidden", hidden, "k", k, "xla", json.dumps(shape["xla_ms"]), flush=True)
        for back, outs, pack in settings:
            M.ROWS_BACK_TILE, M.ROWS_OUT_TILE, M.PACK_TILE = back, outs, pack
            row = {"back_tile": back, "out_tile": outs, "pack_tile": pack,
                   "in_flight": {"moe_rows_back": k * back, "moe_rows_out": outs},
                   "ms": {}, "ns_a_row": {}, "of_floor": {}, "differing_from_xla": {}}
            kernel = sites("kernel")
            kernel["pack_all"] = kernel["pack_tokens"] = each(lambda x: M._pack_rows(x, M.PACK_TILE))
            # the movers alone, on rows packed beforehand
            kernel["rows_back_w"] = each(lambda packed, w, inv: M._rows_back(
                packed, inv, w, TOKENS, hidden, bf16, M.ROWS_BACK_TILE))
            kernel["rows_back"] = each(lambda packed, w, inv: M._rows_back(
                packed, inv, None, TOKENS, hidden, bf16, M.ROWS_BACK_TILE))
            kernel["rows_out"] = each(lambda packed, token_of, rows, w_rows: M._rows_out(
                packed, token_of, rows, w_rows, M.ROWS_OUT_TILE))
            for one in sets:
                one["packed"], one["packed_y"] = (M._pack_rows(one[n], M.PACK_TILE) for n in ("rows", "y"))
            operands["rows_back_w"] = operands["rows_back"] = pick("packed", "w", "inv")
            operands["rows_out"] = pick("packed_y", "token_of", "rows", "w_rows")
            for name, fn in kernel.items():
                try:
                    row["ms"][name] = ms = timed(fn, *operands[name])
                    row["ns_a_row"][name] = ms * 1e6 / (TOKENS if name == "pack_tokens" else k * TOKENS)
                    row["of_floor"][name] = floor_ms[name] / ms
                    if name in want:
                        got = jax.tree.leaves(fn(*operands[name]))
                        row["differing_from_xla"][name] = [
                            int(np.sum(np.asarray(a, np.float32) != np.asarray(b, np.float32)))
                            for a, b in zip(got, jax.tree.leaves(want[name]))]
                except Exception as e:  # a setting the compiler refuses
                    row["ms"][name] = None
                    row.setdefault("errors", {})[name] = str(e)[:400]
            for one in sets:  # 0.3 GB a set
                del one["packed"], one["packed_y"]
            shape["settings"].append(row)
            print(json.dumps(row), flush=True)
        M.ROWS_BACK_TILE, M.ROWS_OUT_TILE, M.PACK_TILE = committed
        out["shapes"].append(shape)
        save(out)
    # every width `rows_form` lets through, at a short block: the three sites against the XLA form
    out["widths"] = {}
    for hidden in range(M.ROWS_MIN_HIDDEN, M.ROWS_MAX_HIDDEN + 1, 256):
        one = operand_set(M, hidden, 2, hidden)

        def three(form):
            run = jax.jit(lambda rows, y, w, order, inv: (
                M._sum_over_k(form, rows, inv, TOKENS, w), M._sum_over_k(form, rows, inv, TOKENS, None),
                M._combine_bwd(form, (rows, w, order, inv), y)[0]))
            return run(*(one[n] for n in ("rows", "y", "w", "order", "inv")))

        assert M.rows_form(True, bf16, hidden, TOKENS, 2) == "kernel"
        out["widths"][hidden] = [int(np.sum(np.asarray(a, np.float32) != np.asarray(b, np.float32)))
                                 for a, b in zip(three("kernel"), three("xla"))]
        print("width", hidden, "differing from xla (sum_w, sum, d_out)", out["widths"][hidden], flush=True)
    save(out)
    # a permutation of scalars three ways
    n = 10 * TOKENS
    order = jax.random.permutation(jax.random.PRNGKey(1), n).astype(jnp.int32)
    slot = jnp.arange(n, dtype=jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(slot)
    values = [jax.random.uniform(jax.random.PRNGKey(c), (n,), jnp.float32) for c in range(CALLS)]
    out["scalars"] = {
        "gather_ms": timed(jax.jit(lambda *vs: [v[order] for v in vs]), *values),
        "scatter_ms": timed(jax.jit(lambda *vs: [jnp.zeros_like(v).at[inverse].set(v) for v in vs]), *values),
        "sort_ms": timed(jax.jit(lambda *vs: [M._permuted(v, inverse) for v in vs]), *values),
        "equal": bool(jnp.array_equal(values[0][order], M._permuted(values[0], inverse)))}
    print("scalars", json.dumps(out["scalars"]), flush=True)

    # the whole block, the kernels against the XLA form
    out["blocks"] = {}
    decide = M.rows_form
    for cell, b in BLOCKS.items():
        if (b["hidden"], b["k"]) not in shapes:
            continue
        keys = jax.random.split(jax.random.PRNGKey(len(cell)), 6)
        held, hidden = b["experts"] if b["held"] is None else b["held"][1], b["hidden"]
        y = jax.random.normal(keys[0], (1, TOKENS, hidden), jnp.float32).astype(bf16)
        operands = (y, jax.random.normal(keys[1], (hidden, b["experts"]), jnp.float32) * 0.02,
                    jax.random.normal(keys[2], (held, hidden, 2 * b["width"]), jnp.float32) * 0.02,
                    jax.random.normal(keys[3], (held, b["width"], hidden), jnp.float32) * 0.02)
        cot = jax.random.normal(keys[4], y.shape, jnp.float32).astype(bf16)
        bias = (jax.random.normal(keys[5], (b["experts"],), jnp.float32) * 0.05
                if b["router"]["score"] == "sigmoid" else None)

        def run(form):
            M.rows_form = lambda *a: form
            try:
                def loss(*ops):
                    got, _ = M.moe_ffn(*ops, experts_per_token=b["k"], dtype=bf16, held=b["held"],
                                       bias=bias, **b["router"])
                    return jnp.sum(got.astype(jnp.float32) * cot.astype(jnp.float32)), got
                (_, got), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(*operands)
                return (got,) + tuple(grads)
            finally:
                M.rows_form = decide

        rows = {}
        for name, a, want in zip(("out", "d_y", "d_router", "d_wi", "d_wo"), run("kernel"), run("xla")):
            a, want = np.asarray(a, np.float64), np.asarray(want, np.float64)
            rows[name] = {"differing": int(np.sum(a != want)), "of": int(a.size),
                          "rel_l2": float(np.linalg.norm(a - want) / np.linalg.norm(want))}
        out["blocks"][cell] = rows
        print(cell, json.dumps(rows), flush=True)
    save(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

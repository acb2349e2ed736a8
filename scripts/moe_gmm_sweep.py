#!/usr/bin/env python3
"""Chip measurement behind ops/moe.py's choices, at OLMoE-1B-7B's shapes
(8192 tokens x 8 experts a token = 65536 rows, 64 experts, hidden 2048,
width 1024). Not a benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/moe_gmm_sweep.py

and read `chiprun_out/moe_gmm_sweep.json`. Times are medians of fenced calls
on one chip. It measures

1. the grouped matmul: `jax.lax.ragged_dot` (XLA:TPU's own kernel) against
   the Pallas megablox `gmm` at several tilings, forward and
   forward + backward, for both of the block's shapes under balanced groups,
   and the two contenders under skewed groups;
2. the whole block (`ops/moe._local_moe`) forward + backward as committed,
   and with the pieces around the matmuls swapped for what autodiff would
   derive (scatter-add transposes), to show what the custom transposes buy.

Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOKENS, K, EXPERTS, HIDDEN, WIDTH = 8192, 8, 64, 2048, 1024
ROWS = TOKENS * K
PEAK = 197e12


def timed(fn, *args, repeat=8):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(repeat):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out) * 1e3


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("moe_gmm_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

    from galvatron_tpu.ops import moe

    key = jax.random.PRNGKey(0)
    results = {"device": jax.devices()[0].device_kind, "rows": ROWS, "gmm": [], "block": {}}

    def groups(kind):
        if kind == "balanced":  # what a random router gives: about 1024 +- 30 a group
            experts = jax.random.randint(key, (ROWS,), 0, EXPERTS)
        else:  # half of all rows in 4 experts
            hot = jax.random.randint(key, (ROWS // 2,), 0, 4)
            experts = jnp.concatenate([hot, jax.random.randint(key, (ROWS // 2,), 4, EXPERTS)])
        return jnp.bincount(experts, length=EXPERTS).astype(jnp.int32)

    impls = {"ragged_dot": lambda: (lambda x, w, g: jax.lax.ragged_dot(x, w, g))}
    for tiling in ((128, 128, 128), (512, 512, 512), moe.GMM_TILING, (1024, 512, 1024),
                   (256, 1024, 1024), (1024, 1024, 1024), (512, 2048, 1024), (512, 1024, 2048),
                   (512, 2048, 2048)):
        impls["megablox%s" % (tiling,)] = lambda tiling=tiling: (
            lambda x, w, g: megablox_gmm(x, w, g, preferred_element_type=jnp.bfloat16,
                                         tiling=tiling))
    committed_tiling = "megablox%s" % (moe.GMM_TILING,)
    for shape_name, (kdim, ndim) in (("in", (HIDDEN, 2 * WIDTH)), ("out", (WIDTH, HIDDEN))):
        x = jax.random.normal(key, (ROWS, kdim), jnp.bfloat16)
        w = jax.random.normal(key, (EXPERTS, kdim, ndim), jnp.bfloat16) * 0.02
        flops = 2.0 * ROWS * kdim * ndim
        for skew in ("balanced", "skewed"):
            g = groups(skew)
            for name, make in impls.items():
                if skew == "skewed" and name not in ("ragged_dot", committed_tiling):
                    continue
                fn = make()
                row = {"impl": name, "shape": shape_name, "groups": skew}
                try:
                    fwd = jax.jit(fn)
                    row["fwd_ms"] = timed(fwd, x, w, g)
                    row["fwd_roofline_pct"] = 100 * flops / PEAK / (row["fwd_ms"] / 1e3)
                    both = jax.jit(jax.grad(
                        lambda x, w, g: jnp.sum(fn(x, w, g).astype(jnp.float32)), argnums=(0, 1)))
                    row["fwd_bwd_ms"] = timed(both, x, w, g)
                    row["fwd_bwd_roofline_pct"] = 100 * 3 * flops / PEAK / (row["fwd_bwd_ms"] / 1e3)
                except Exception as e:  # a tiling the kernel refuses
                    row["error"] = "%s: %s" % (type(e).__name__, str(e)[:200])
                results["gmm"].append(row)
                print(json.dumps(row), flush=True)

    # ---------------------------------------------------------- the block
    y = jax.random.normal(key, (TOKENS, HIDDEN), jnp.bfloat16)
    router = jax.random.normal(key, (HIDDEN, EXPERTS), jnp.float32) * 0.02
    wi = jax.random.normal(key, (EXPERTS, HIDDEN, 2 * WIDTH), jnp.float32) * 0.02
    wo = jax.random.normal(key, (EXPERTS, WIDTH, HIDDEN), jnp.float32) * 0.02

    def block_loss(y, router, wi, wo):
        out, aux = moe._local_moe(y, router, None, wi, wo, k=K, norm_topk_prob=False,
                                  activate=moe.swiglu, dtype=jnp.bfloat16, on_tpu=True)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux["load_balance"] + aux["router_z"]

    def measure_block(label):
        fwd = jax.jit(block_loss)
        both = jax.jit(jax.grad(block_loss, argnums=(0, 1, 2, 3)))
        results["block"][label] = {"fwd_ms": timed(fwd, y, router, wi, wo),
                                   "fwd_bwd_ms": timed(both, y, router, wi, wo)}
        print(label, json.dumps(results["block"][label]), flush=True)

    measure_block("as_committed")
    committed = moe._dispatch, moe._combine
    # what autodiff derives: the gathers' transposes as scatter-adds
    moe._dispatch = lambda y, order, inv_order: y[order % y.shape[0]]
    moe._combine = lambda out, weights, order, inv_order: moe._sum_over_k(
        out, inv_order, weights.shape[0], weights)
    measure_block("autodiff_scatter_transposes")
    moe._dispatch, moe._combine = committed

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "moe_gmm_sweep.json"), "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

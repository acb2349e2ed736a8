#!/usr/bin/env python3
"""Chip measurement behind the window kernels' block size
(ops/window_attention.py `BLOCK`), at the Laguna cell's
shapes (1 x 8192 tokens, 64 query heads on 8 KV heads of 128, a window of 512,
bf16). Not a benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/window_attn_sweep.py [block ...]

and read `chiprun_out/window_attn_sweep.json`. Times are medians of fenced
calls on one chip, forward and forward + backward (the gradient of a sum of
squares in q, k, v), for each setting given, the committed one first, with the
least time the band's operations and bytes allow
(benchmarks/model_flops/laguna.py `window_kernel_cost`) beside them; then the
committed setting **at 16384 tokens** (a kernel that follows the band takes
twice its 8192 time, one that walks the triangle four times), the flash
kernels on the whole causal triangle at the same 64 heads (what a mask alone
would pay), and the committed kernels' output and gradients against the band
mask on XLA's logits at ONE key head's 8 query heads (2 GiB of logits).
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

BATCH, TOKENS, Q_HEADS, KV_HEADS, HEAD_DIM, WINDOW = 1, 8192, 64, 8, 128, 512
DEFAULT = [512, 256, 1024]


def timed(fn, *args, repeat=10):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(repeat):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out) * 1e3


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("window_attn_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells, flops
    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import window_attention as W

    peak = cells.load_json(ROOT, "benchmarks/peaks.json")[jax.devices()[0].device_kind]
    costs = cells.load_module(ROOT, "benchmarks/model_flops/laguna.py")
    fields = {"window_num_heads": Q_HEADS, "num_kv_heads": KV_HEADS, "head_dim": HEAD_DIM, "sliding_window": WINDOW}
    committed = W.BLOCK
    settings = [int(a) for a in argv] or DEFAULT
    settings = [committed] + [s for s in settings if s != committed]
    scale = HEAD_DIM ** -0.5

    def operands(tokens, q_heads=Q_HEADS, kv_heads=KV_HEADS):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(key, (BATCH, tokens, heads, HEAD_DIM), jnp.bfloat16)
                     for key, heads in zip(ks, (q_heads, kv_heads, kv_heads)))

    def least_ms(tokens, kinds):
        return sum(flops.least_time_s(costs.window_kernel_cost(fields, kind, BATCH, tokens), peak)[0]
                   for kind in kinds) * 1e3

    def window_times(setting, tokens):
        W.BLOCK = setting
        fwd = jax.jit(lambda q, k, v: A._pallas_window(q, k, v, window=WINDOW, sm_scale=scale))
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            A._pallas_window(q, k, v, window=WINDOW, sm_scale=scale).astype(jnp.float32) ** 2), (0, 1, 2)))
        qkv = operands(tokens)
        try:
            row = {"block": setting, "tokens": tokens, "fwd_ms": timed(fwd, *qkv),
                   "fwd_bwd_ms": timed(both, *qkv)}
        except Exception as e:  # a size Mosaic refuses
            return {"block": setting, "tokens": tokens, "refused": str(e).splitlines()[0][:200]}
        row["fwd_least_ms"], row["fwd_bwd_least_ms"] = least_ms(tokens, ["fwd"]), least_ms(tokens, ["fwd", "bwd"])
        row["fwd_roofline_pct"] = 100 * row["fwd_least_ms"] / row["fwd_ms"]
        row["fwd_bwd_roofline_pct"] = 100 * row["fwd_bwd_least_ms"] / row["fwd_bwd_ms"]
        return row

    out = {"device": jax.devices()[0].device_kind, "committed": committed, "settings": []}
    for setting in settings:
        out["settings"].append(window_times(setting, TOKENS))
        print(json.dumps(out["settings"][-1]), flush=True)
    long = window_times(committed, 2 * TOKENS)
    short = out["settings"][0]
    out["at_16384"] = {**long, "fwd_over_8192": long["fwd_ms"] / short["fwd_ms"],
                       "fwd_bwd_over_8192": long["fwd_bwd_ms"] / short["fwd_bwd_ms"]}
    print(json.dumps(out["at_16384"]), flush=True)

    # the whole causal triangle at the same heads: what a mask alone would pay
    q, k, v = operands(TOKENS)
    tri = lambda q, k, v: A.core_attention(q, k, v, causal=True, impl="flash", sm_scale=scale)  # noqa: E731
    out["flash_triangle"] = {
        "fwd_ms": timed(jax.jit(tri), q, k, v),
        "fwd_bwd_ms": timed(jax.jit(jax.grad(lambda q, k, v: jnp.sum(tri(q, k, v).astype(jnp.float32) ** 2), (0, 1, 2))),
                            q, k, v)}
    print(json.dumps(out["flash_triangle"]), flush=True)

    # the committed kernels against the band mask on XLA's logits, one key head's share
    W.BLOCK = committed
    q, k, v = operands(TOKENS, Q_HEADS // KV_HEADS, 1)
    probe = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)

    def run(impl):
        def of(q, k, v):
            o = A.core_attention(q, k, v, window=WINDOW, impl=impl, sm_scale=scale)
            return jnp.sum(o.astype(jnp.float32) * probe), o
        (_, o), grads = jax.jit(jax.value_and_grad(of, (0, 1, 2), has_aux=True))(q, k, v)
        return [np.asarray(t, np.float64) for t in (o,) + tuple(grads)]

    rel = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))  # noqa: E731
    out["against_xla_band"] = dict(zip(("out", "dq", "dk", "dv"), map(rel, run("flash"), run("xla"))))
    print(json.dumps(out["against_xla_band"]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_attn_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

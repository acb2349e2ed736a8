#!/usr/bin/env python3
"""Chip measurement behind the window kernels' block size
(ops/window_attention.py `BLOCK`) and their form, at the Laguna cell's
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

**The surround (PR 50)**: from q as its projection wrote it to what `wo`
reads, with rope and the head's gate (and back: the gradients of q, k, v and
the gate logits), by form: `as_projected` (the kernels turn q in VMEM and
gate their output: what a TPU runs), `rope_in_kernel` and `gate_in_kernel`
(one of the two, the other a pass of its own), `passes_before_and_after` (the
same kernels on q turned by `apply_rotary` before the call, the gate's product
after it: what a rotation without tables pays), and, where the parent's
checkout lies under `_parent/` (scripts/chip_pairs.sh's place), `parent` (its
kernels in their (batch, heads, seq, 128) layout behind the transposes) and
those kernels alone on operands already in that layout. The
kernels keep nothing of the forward for the gate's backward and make nothing
again (`delta = sum_j p dp` is `sum_d` of the gated cotangent x the ungated
output), so there is no kept-against-remade pair to time.
Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import importlib.util
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
        """q, k, v as their projections write them: (batch, tokens, heads x 128)."""
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(key, (BATCH, tokens, heads * HEAD_DIM), jnp.bfloat16)
                     for key, heads in zip(ks, (q_heads, kv_heads, kv_heads)))

    by_heads = lambda t: t.reshape(t.shape[:2] + (-1, HEAD_DIM))  # noqa: E731
    flat = lambda t: t.reshape(t.shape[:2] + (-1,))  # noqa: E731
    rel = lambda a, b: float(np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b, np.float64))  # noqa: E731
                             / np.linalg.norm(np.asarray(b, np.float64)))

    def least_ms(tokens, kinds):
        return sum(flops.least_time_s(costs.window_kernel_cost(fields, kind, BATCH, tokens), peak)[0]
                   for kind in kinds) * 1e3

    def window_times(setting, tokens):
        W.BLOCK = setting
        alone = lambda q, k, v: W.window_attention(q, k, v, None, None, WINDOW, scale,  # noqa: E731
                                                   W.block_for(tokens, WINDOW), HEAD_DIM)
        fwd = jax.jit(alone)
        both = jax.jit(jax.grad(lambda q, k, v: jnp.sum(alone(q, k, v).astype(jnp.float32) ** 2), (0, 1, 2)))
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

    # the surround: q as projected -> what wo reads, rope and the head's gate with it, three forms
    from galvatron_tpu.ops import rope as R

    W.BLOCK = committed
    q, k, v = operands(TOKENS)
    logits = jax.random.normal(jax.random.PRNGKey(7), (BATCH, TOKENS, Q_HEADS), jnp.bfloat16)
    positions = jnp.arange(TOKENS)[None]
    parent_path = os.path.join(ROOT, "_parent", "galvatron_tpu", "ops", "window_attention.py")
    parent = None
    if os.path.exists(parent_path):
        spec = importlib.util.spec_from_file_location("parent_window_attention", parent_path)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    def surround(form):
        def run(q, k, v, logits):
            q, k, v = by_heads(q), R.apply_rotary(by_heads(k), positions), by_heads(v)
            if form == "parent":
                out = parent.window_attention(*(t.transpose(0, 2, 1, 3) for t in (R.apply_rotary(q, positions), k, v)),
                                              WINDOW, scale, committed).transpose(0, 2, 1, 3)
                return flat(out * jax.nn.sigmoid(logits)[..., None])
            turn, gate = form in ("as_projected", "rope_in_kernel"), form in ("as_projected", "gate_in_kernel")
            out = A._pallas_window(q if turn else R.apply_rotary(q, positions), k, v, window=WINDOW, sm_scale=scale,
                                   q_rope=R.half_split_tables(positions, HEAD_DIM) if turn else None,
                                   head_gate=logits if gate else None)
            return flat(out if gate else out * jax.nn.sigmoid(logits)[..., None])
        return run

    squares = lambda form: lambda *a: jnp.sum(surround(form)(*a).astype(jnp.float32) ** 2)  # noqa: E731
    out["surround"] = {}
    for form in ["as_projected", "rope_in_kernel", "gate_in_kernel", "passes_before_and_after"] + ["parent"] * bool(parent):
        out["surround"][form] = {"fwd_ms": timed(jax.jit(surround(form)), q, k, v, logits),
                                 "fwd_bwd_ms": timed(jax.jit(jax.grad(squares(form), (0, 1, 2, 3))), q, k, v, logits)}
        print(form, json.dumps(out["surround"][form]), flush=True)
    if parent:  # the parent's kernels alone on operands already in THEIR layout: what the layout itself costs a kernel
        alone = lambda q, k, v: parent.window_attention(q, k, v, WINDOW, scale, committed)  # noqa: E731
        theirs = [by_heads(t).transpose(0, 2, 1, 3) for t in (q, k, v)]
        out["parent_kernels_alone"] = {
            "fwd_ms": timed(jax.jit(alone), *theirs),
            "fwd_bwd_ms": timed(jax.jit(jax.grad(lambda q, k, v: jnp.sum(alone(q, k, v).astype(jnp.float32) ** 2),
                                                 (0, 1, 2))), *theirs)}
        print("parent_kernels_alone", json.dumps(out["parent_kernels_alone"]), flush=True)
    got, want = (jax.jit(jax.value_and_grad(squares(form), (0, 1, 2, 3)))(q, k, v, logits)
                 for form in ("as_projected", "passes_before_and_after"))
    out["surround"]["as_projected_against_passes"] = dict(
        zip(("loss", "dq", "dk", "dv", "dlogits"), [rel(got[0], want[0])] + list(map(rel, got[1], want[1]))))
    print(json.dumps(out["surround"]["as_projected_against_passes"]), flush=True)

    # the whole causal triangle at the same heads: what a mask alone would pay
    q, k, v = operands(TOKENS)
    tri = lambda q, k, v: A.core_attention(  # noqa: E731
        *map(by_heads, (q, k, v)), causal=True, impl="flash", sm_scale=scale)
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
            o = flat(A.core_attention(*map(by_heads, (q, k, v)), window=WINDOW, impl=impl, sm_scale=scale))
            return jnp.sum(o.astype(jnp.float32) * probe), o
        (_, o), grads = jax.jit(jax.value_and_grad(of, (0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + tuple(grads)

    out["against_xla_band"] = dict(zip(("out", "dq", "dk", "dv"), map(rel, run("flash"), run("xla"))))
    print(json.dumps(out["against_xla_band"]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "window_attn_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

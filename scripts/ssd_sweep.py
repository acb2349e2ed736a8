#!/usr/bin/env python3
"""Chip measurement behind the sizes of Mamba-2's scan (ops/ssd.py `CHUNK`,
`HEADS_AT_ONCE`) and behind the attention dispatch at 64-wide heads
(ops/attention.py), at the Granite-4.0-H cell's widths (1 x 4096 tokens, 64
heads of 64 with states of 128, one group; 32 query heads on 8 KV heads of
64; bf16). Not a benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/ssd_sweep.py [chunk,heads_at_once ...]
    chiprun -- python3 scripts/ssd_sweep.py --groups 8 --tokens 8192 [chunk,heads_at_once ...]

and read `chiprun_out/ssd_sweep.json`. With `--groups G` (and `--tokens T`) the
scan alone at Nemotron-H's form, B and C of (T, G, 128) and the heads worked at
once inside one group (at most 64 / G of them), written to
`chiprun_out/ssd_sweep_groups.json`; the attention part is Granite's and is
left out. Times are medians of fenced calls on
one chip: the scan alone, forward and forward + backward (the gradient of a
sum of squares in x, dt, A, B, C, D), for each setting given, the committed one
first, with the least time the recurrence's operations and bytes allow
(benchmarks/model_flops/granite_hybrid.py `ssd_cost`) beside them; and the
flash kernels at head_dim 64 as they are against the same call with q, k, v
zero-padded to 128 and against XLA's attention, forward + backward, with how
far each lies from XLA's. Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, TOKENS, HEADS, HEAD_DIM, STATE = 1, 4096, 64, 64, 128
Q_HEADS, KV_HEADS, ATTN_SCALE = 32, 8, 0.015625
DEFAULT = [(128, 16), (128, 8), (128, 32), (128, 64), (64, 16), (256, 16)]
DEFAULT_GROUPS = [(128, 8), (128, 4), (64, 8), (256, 8)]  # the heads at once lie inside a group of 8


def _option(argv, name, default):
    """`name N` taken out of argv -> N."""
    if name not in argv:
        return default
    at = argv.index(name)
    value = int(argv[at + 1])
    del argv[at:at + 2]
    return value


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

    if jax.devices()[0].platform != "tpu":
        print("ssd_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells, flops
    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops.ssd import ssd_scan

    argv = list(argv)
    groups, tokens = _option(argv, "--groups", 1), _option(argv, "--tokens", TOKENS)
    peak = cells.load_json(ROOT, "benchmarks/peaks.json")[jax.devices()[0].device_kind]
    costs = cells.load_module(ROOT, "benchmarks/model_flops/%s.py" % ("granite_hybrid" if groups == 1 else "nemotron_h"))
    fields = {"ssm_num_heads": HEADS, "ssm_head_dim": HEAD_DIM, "ssm_state_dim": STATE, "ssm_groups": groups}
    least = {w: flops.least_time_s(costs.ssd_cost(fields, BATCH * tokens, w), peak)[0] * 1e3
             for w in ("fwd", "bwd")}
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (BATCH, tokens, HEADS, HEAD_DIM), jnp.float32).astype(bf16)
    dt = jnp.exp(jax.random.uniform(ks[1], (BATCH, tokens, HEADS), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
    a = -jax.random.uniform(ks[2], (HEADS,), jnp.float32, 1.0, 16.0)
    bc_shape = (BATCH, tokens, STATE) if groups == 1 else (BATCH, tokens, groups, STATE)
    bm, cm = (jax.random.normal(k, bc_shape, jnp.float32).astype(bf16) for k in ks[3:5])
    d = jnp.ones((HEADS,), jnp.float32)

    settings = [tuple(int(v) for v in arg.split(",")) for arg in argv] or (DEFAULT if groups == 1 else DEFAULT_GROUPS)
    out = {"tokens": tokens, "groups": groups, "least_ms": least, "ssd": [], "attention": {}}
    for chunk, at_once in settings:
        scan = lambda *ops: ssd_scan(*ops, chunk=chunk, heads_at_once=at_once)[0]  # noqa: E731
        loss = lambda *ops: jnp.sum(jnp.square(scan(*ops).astype(jnp.float32)))  # noqa: E731
        row = {"chunk": chunk, "heads_at_once": at_once}
        try:
            row["fwd_ms"] = timed(jax.jit(scan), x, dt, a, bm, cm, d)
            row["fwd_bwd_ms"] = timed(jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))), x, dt, a, bm, cm, d)
        except Exception as e:  # a setting the compiler refuses is a result
            row["error"] = str(e)[:300]
        out["ssd"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    if groups > 1:
        with open(os.path.join(ROOT, "chiprun_out", "ssd_sweep_groups.json"), "w") as f:
            json.dump(out, f, indent=1)
        return 0

    q = jax.random.normal(ks[5], (BATCH, tokens, Q_HEADS, HEAD_DIM), jnp.float32).astype(bf16)
    k, v = (jax.random.normal(kk, (BATCH, tokens, KV_HEADS, HEAD_DIM), jnp.float32).astype(bf16)
            for kk in ks[6:8])

    def padded(q, k, v, **kw):
        wide = lambda t: jnp.pad(t, ((0, 0),) * 3 + ((0, 128 - HEAD_DIM),))  # noqa: E731
        return A.core_attention(wide(q), wide(k), wide(v), **kw)[..., :HEAD_DIM]

    forms = {
        "flash_64": lambda q, k, v: A.core_attention(q, k, v, sm_scale=ATTN_SCALE, impl="flash"),
        "flash_padded_128": lambda q, k, v: padded(q, k, v, sm_scale=ATTN_SCALE, impl="flash"),
        "xla": lambda q, k, v: A.core_attention(q, k, v, sm_scale=ATTN_SCALE, impl="xla"),
    }
    results = {}
    for name, form in forms.items():
        grad = jax.jit(jax.value_and_grad(
            lambda q, k, v, form=form: jnp.sum(jnp.square(form(q, k, v).astype(jnp.float32))),
            argnums=(0, 1, 2)))
        results[name] = jax.block_until_ready(grad(q, k, v))
        out["attention"][name] = {"fwd_ms": timed(jax.jit(form), q, k, v), "fwd_bwd_ms": timed(grad, q, k, v)}
    for name in ("flash_64", "flash_padded_128"):
        ref = results["xla"][1]
        out["attention"][name]["grad_rel_err_vs_xla"] = [
            float(jnp.linalg.norm((g - r).astype(jnp.float32)) / jnp.linalg.norm(r.astype(jnp.float32)))
            for g, r in zip(results[name][1], ref)]
    print(json.dumps(out["attention"]), flush=True)
    with open(os.path.join(ROOT, "chiprun_out", "ssd_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python3
"""Chip measurement behind the sizes of Mamba-2's scan (ops/ssd.py `CHUNK`,
`HEADS_AT_ONCE` of the XLA form, `CHANNELS` a block of the kernels) and behind
the attention dispatch at 64-wide heads (ops/attention.py), at the
Granite-4.0-H cell's widths (1 x 4096 tokens, 64 heads of 64 with states of
128, one group; 32 query heads on 8 KV heads of 64; bf16). Not a benchmark
cell: run by hand through the chip tool,

    chiprun -- python3 scripts/ssd_sweep.py [impl:chunk,heads ...]
    chiprun -- python3 scripts/ssd_sweep.py --groups 8 --tokens 8192 [impl:chunk,heads ...]

and read `chiprun_out/ssd_sweep.json`. A setting is a form (`impl`: "xla" or
"pallas"), the tokens a chunk and the heads worked together (the XLA form's
`heads_at_once`; the kernels' heads a block, of one group or whole groups);
with none given, both forms at the committed sizes first and then their
neighbours. With `--groups G` (and `--tokens T`) the scan alone at
Nemotron-H's form, B and C of (T, G, 128), written to
`chiprun_out/ssd_sweep_groups.json`; the attention part is Granite's and is
left out (`--scan-only` leaves it out of Granite's too). Times are of calls
queued back to back on one chip and fenced once a batch (`timed`; until PR 72
a fence a call, which read 0.5 ms and more over the device's time): the scan
alone, forward and forward + backward (the gradient of a sum of squares in x,
dt, A, B, C, D), for each setting given, with the least time the recurrence's
operations and bytes allow (benchmarks/model_flops/granite_hybrid.py
`ssd_cost`) beside them, the counter it hands back and how far each setting's
output and gradients lie from the first setting's; and the flash kernels at
head_dim 64 as they are against the same call with q, k, v zero-padded to 128
and against XLA's attention, forward + backward, with how far each lies from
XLA's. Refuses to run where jax finds no TPU.
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
# the committed sizes of both forms first, then their neighbours
DEFAULT = [("pallas", 128, 32), ("xla", 128, 16), ("pallas", 128, 8), ("pallas", 128, 16), ("pallas", 128, 64),
           ("pallas", 256, 32), ("xla", 128, 8), ("xla", 256, 16)]
# the XLA form's heads worked together lie inside a group of 8; the kernels' block is whole groups
DEFAULT_GROUPS = [("pallas", 128, 32), ("xla", 128, 8), ("pallas", 128, 8), ("pallas", 128, 16), ("pallas", 128, 64),
                  ("pallas", 256, 32), ("xla", 256, 8), ("xla", 128, 4)]


def _option(argv, name, default):
    """`name N` taken out of argv -> N."""
    if name not in argv:
        return default
    at = argv.index(name)
    value = int(argv[at + 1])
    del argv[at:at + 2]
    return value


def timed(fn, *args, repeat=20, rounds=5):
    """Milliseconds a call: `repeat` calls queued back to back and fenced
    ONCE, the median of `rounds` such (a fence a call adds the host's dispatch
    and the wait, half a millisecond and more, to a kernel of one)."""
    import jax

    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(rounds):
        t = time.perf_counter()
        for _ in range(repeat):
            result = fn(*args)
        jax.block_until_ready(result)
        out.append((time.perf_counter() - t) / repeat)
    return statistics.median(out) * 1e3


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("ssd_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells, flops
    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import ssd

    argv = list(argv)
    groups, tokens = _option(argv, "--groups", 1), _option(argv, "--tokens", TOKENS)
    scan_only = "--scan-only" in argv
    if scan_only:
        argv.remove("--scan-only")
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

    def setting(arg):
        impl, sizes = arg.split(":")
        return (impl,) + tuple(int(v) for v in sizes.split(","))

    settings = [setting(arg) for arg in argv] or (DEFAULT if groups == 1 else DEFAULT_GROUPS)
    out = {"tokens": tokens, "groups": groups, "least_ms": least, "ssd": [], "attention": {}}
    first = None
    for impl, chunk, heads in settings:
        ssd.CHANNELS = heads * HEAD_DIM  # the kernels' heads a block; the XLA form's `heads_at_once`
        scan = lambda *ops: ssd.ssd_scan(*ops, chunk=chunk, heads_at_once=heads, impl=impl)[0]  # noqa: E731
        loss = lambda *ops: jnp.sum(jnp.square(scan(*ops).astype(jnp.float32)))  # noqa: E731
        row = {"impl": impl, "chunk": chunk, "heads": heads}
        try:
            fwd, grad = jax.jit(scan), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)))
            row["fwd_ms"] = timed(fwd, x, dt, a, bm, cm, d)
            row["fwd_bwd_ms"] = timed(grad, x, dt, a, bm, cm, d)
            got = (fwd(x, dt, a, bm, cm, d),) + grad(x, dt, a, bm, cm, d)
            row["state_abs_max"] = float(jax.jit(lambda *ops: ssd.ssd_scan(
                *ops, chunk=chunk, heads_at_once=heads, impl=impl)[2])(x, dt, a, bm, cm, d))
            first = first or got
            row["off_the_first"] = [float(jnp.linalg.norm((g - w).astype(jnp.float32))
                                          / jnp.linalg.norm(w.astype(jnp.float32))) for g, w in zip(got, first)]
        except Exception as e:  # a setting the compiler refuses is a result
            row["error"] = str(e)[:300]
        out["ssd"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    if groups > 1 or scan_only:
        with open(os.path.join(ROOT, "chiprun_out", "ssd_sweep%s.json" % ("_groups" if groups > 1 else "")), "w") as f:
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

#!/usr/bin/env python3
"""Chip measurement of the per-channel delta rule's core (ops/linear_attention.py
`kda_rule`: the kernels `kda_fwd` / `kda_bwd` beside the XLA form), at the
Kimi-Linear cell's widths (one row of 8192 tokens, 32 heads of 128 x 128,
bf16 q, k, v, float32 g and beta). Not a benchmark cell: run by hand through
the chip tool,

    chiprun -- python3 scripts/kda_kernel_sweep.py [block ...]

and read `chiprun_out/kda_kernel_sweep.json`. Times are medians of fenced
calls on one chip: the forward alone, and the forward that keeps its
residuals with the backward behind it, for the kernels at each `_BLOCK` given
(tiles a grid step walks; the committed one first) and for the XLA form;
beside them how far the kernels' outputs, final states and five gradients lie
from the XLA form's on the same operands. Refuses to run where jax finds no
TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TOKENS, HEADS, WIDTH = 8192, 32, 128


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
        print("kda_kernel_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from galvatron_tpu.ops import linear_attention as L

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    shape = (1, TOKENS, HEADS, WIDTH)
    q = (unit(jax.random.normal(ks[0], shape, jnp.float32)) * WIDTH ** -0.5).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], shape, jnp.float32)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], shape, jnp.float32).astype(jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], shape, jnp.float32, jnp.log(1e-3), jnp.log(1.5)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3], jnp.float32))
    ops = (q, k, v, g, beta)

    def objective(impl):
        def of(*a):
            o, last = L.kda_rule(*a, impl=impl)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(jnp.cos(last))
        return of

    def forms(impl):
        return (jax.jit(lambda *a: L.kda_rule(*a, impl=impl)),
                jax.jit(jax.grad(objective(impl), argnums=range(5))))

    def worst(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    report = {"device": jax.devices()[0].device_kind, "tokens": TOKENS, "heads": HEADS, "width": WIDTH}
    fwd, grad = forms("xla")
    report["xla"] = {"fwd_ms": timed(fwd, *ops), "fwd_bwd_ms": timed(grad, *ops)}
    want = fwd(*ops) + grad(*ops)
    for block in [int(a) for a in argv] or [L._BLOCK]:
        L._BLOCK = block
        fwd, grad = forms("pallas")
        got = fwd(*ops) + grad(*ops)
        report["pallas_block_%d" % block] = {
            "fwd_ms": timed(fwd, *ops), "fwd_bwd_ms": timed(grad, *ops),
            "from_xla": {name: worst(a, b) for name, a, b in zip("o last dq dk dv dg dbeta".split(), got, want)},
            "finite": all(bool(jnp.all(jnp.isfinite(a.astype(jnp.float32)))) for a in got)}
        print(json.dumps({("pallas_block_%d" % block): report["pallas_block_%d" % block]}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kda_kernel_sweep.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

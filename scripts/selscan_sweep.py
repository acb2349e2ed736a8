#!/usr/bin/env python3
"""Mamba-1's selective scan alone on the chip at the Phi-4-mini-flash cell's
widths (`ops/selective_scan.selective_scan`: one 8192-token sequence, 5120
channels, states of 16, bf16 x / B / C, float32 dt), for a list of settings:

    chiprun -- python3 scripts/selscan_sweep.py [chunk,block ...]

`chunk`: the tokens a chunk (`CHUNK`); `block`: the positions whose states the
backward holds at once (`BLOCK`). A setting reads the forward and forward +
backward (the gradient of sum(m x a fixed weight) in all six operands) in ms,
the median of `--runs` after a warm-up, and the compiled forward + backward's
temporaries. The first setting is the committed one. Refuses to run where jax
finds no TPU. What PERF.md's section 7 ("Selective-scan layers") quotes."""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
TOKENS, CHANNELS, STATES = 8192, 5120, 16


def main(argv) -> int:
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.ops import selective_scan as op

    if jax.devices()[0].platform != "tpu":
        print("selscan_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    settings = [tuple(int(n) for n in a.split(",")) for a in argv] or [(op.CHUNK, op.BLOCK)]
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (1, TOKENS, CHANNELS), jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(ks[1], (1, TOKENS, CHANNELS), jnp.float32, math.log(1e-3), math.log(0.1)))
    a = -jnp.broadcast_to(jnp.arange(1.0, STATES + 1), (CHANNELS, STATES))
    b, c = (jax.random.normal(k, (1, TOKENS, STATES), jnp.bfloat16) for k in ks[2:4])
    d = jnp.ones((CHANNELS,), jnp.float32)
    weight = jax.random.normal(ks[4], (1, TOKENS, CHANNELS), jnp.bfloat16)
    operands = (x, dt, a, b, c, d)

    def timed(fn, runs=5):
        jax.block_until_ready(fn(*operands))
        took = []
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            took.append((time.perf_counter() - t0) * 1e3)
        return statistics.median(took)

    for chunk, block in settings:
        op.BLOCK = block
        fwd = jax.jit(lambda *o: op.selective_scan(*o, chunk=chunk)[0])
        both = jax.jit(jax.grad(lambda *o: jnp.sum((op.selective_scan(*o, chunk=chunk)[0] * weight).astype(jnp.float32)),
                                argnums=tuple(range(6))))
        temp = both.lower(*operands).compile().memory_analysis().temp_size_in_bytes
        print(json.dumps({"chunk": chunk, "block": block, "fwd_ms": round(timed(fwd), 3),
                          "fwd_bwd_ms": round(timed(both), 3), "fwd_bwd_temp_mib": round(temp / 2 ** 20, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

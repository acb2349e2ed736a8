#!/usr/bin/env python3
"""Mamba-1's selective scan alone on the chip at the Phi-4-mini-flash cell's
widths (`ops/selective_scan.selective_scan`: one 8192-token sequence, 5120
channels, states of 16, bf16 x / B / C, float32 dt), for a list of settings:

    chiprun -- python3 scripts/selscan_sweep.py [impl,chunk,size[,unroll] ...]

`impl`: "xla" or "pallas"; `chunk`: the tokens a chunk (`CHUNK`); `size`: for
the XLA form the positions whose states its backward holds at once (`BLOCK`),
for the kernels the channels a grid step holds (`CHANNELS`); `unroll`: the
positions a trip of the kernels' loops (`UNROLL`). A setting reads the forward
and forward + backward (the gradient of sum(m x a fixed weight) in all six
operands) in ms, the median of five after a warm-up, the compiled forward +
backward's temporaries, and how far its output, its final states and its
worst gradient lie from the first row's (a share of the largest magnitude). The XLA form's
committed setting is always the first row, the kernels' the second where none
is named. Refuses to run where jax finds no TPU. What PERF.md's section 7
("Selective-scan layers") quotes."""

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
    settings = [("xla", op.CHUNK, op.BLOCK)] + ([tuple(a.split(",")) for a in argv]
                                                or [("pallas", op.CHUNK, op.CHANNELS)])
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

    def off(got, want):
        got, want = (t.astype(jnp.float32) for t in (got, want))
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    first = None
    for impl, chunk, size, *unroll in settings:
        chunk, size = int(chunk), int(size)
        if impl == "xla":
            op.BLOCK = size
        else:
            op.CHANNELS, op.UNROLL = size, int(unroll[0]) if unroll else op.UNROLL
        jax.clear_caches()  # the kernels' callers are traced once a shape, whatever the module's sizes read now
        fwd = jax.jit(lambda *o: op.selective_scan(*o, chunk=chunk, impl=impl)[0])
        both = jax.jit(jax.grad(lambda *o: jnp.sum((op.selective_scan(*o, chunk=chunk, impl=impl)[0] * weight)
                                                   .astype(jnp.float32)), argnums=tuple(range(6))))
        temp = both.lower(*operands).compile().memory_analysis().temp_size_in_bytes
        row = {"impl": impl, "chunk": chunk, "block" if impl == "xla" else "channels": size,
               "fwd_ms": round(timed(fwd), 3), "fwd_bwd_ms": round(timed(both), 3),
               "fwd_bwd_temp_mib": round(temp / 2 ** 20, 1)}
        if impl == "pallas":
            row["unroll"] = op.UNROLL
        got = jax.jit(lambda *o: op.selective_scan(*o, chunk=chunk, impl=impl)[:2])(*operands) + tuple(both(*operands))
        if first is None:
            first = got
        else:
            row["m_off"], row["final_state_off"] = off(got[0], first[0]), off(got[1], first[1])
            row["worst_grad_off"] = max(off(g, w) for g, w in zip(got[2:], first[2:]))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python3
"""Chip measurement behind the sizes of the passes around the delta rule's
core (ops/linear_attention.py: `conv_norm_fwd` / `conv_norm_bwd`,
`gated_norm_fwd` / `gated_norm_bwd`), at the Qwen3-Next cell's widths (8192
tokens, 16 key heads serving 32 value heads, 128 wide, 4 taps, bf16; four rows
of the batch a call and the time a row, so that a call's dispatch, a fifth of
a millisecond and more, weighs a quarter). Not a
benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/linear_passes_sweep.py [tokens,lanes,at_once ...]

and read `chiprun_out/linear_passes_sweep.json`. Times are medians of fenced
calls on one chip, a pass alone (a call of each of its segments), for each
setting of (`_TOKENS`, `_LANES`, `_AT_ONCE`) given, the committed one first;
beside them the XLA form of the same arithmetic (models/base.linear_mixer's),
forward and forward + backward, and how far each kernel's results lie from
it. Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, TOKENS, KEY_HEADS, VALUE_HEADS, WIDTH, TAPS, EPS = 4, 8192, 16, 32, 128, 4, 1e-6
HBM = 819e9


def timed(fn, *args, repeat=10):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(repeat):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t)
    return statistics.median(out) * 1e3 / BATCH


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("linear_passes_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from galvatron_tpu.ops import linear_attention as L
    from galvatron_tpu.ops.norms import rms_norm

    heads = L.Heads(KEY_HEADS, WIDTH, VALUE_HEADS, WIDTH)
    keys, values = KEY_HEADS * WIDTH, VALUE_HEADS * WIDTH
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    bf16 = jnp.bfloat16
    qkvz = jax.random.normal(ks[0], (BATCH, TOKENS, 2 * keys + 2 * values), jnp.float32).astype(bf16)
    taps = jax.random.uniform(ks[1], (2 * keys + values, TAPS), jnp.float32, -0.5, 0.5)
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (WIDTH,), jnp.float32)
    o = jax.random.normal(ks[3], (BATCH, TOKENS, values), jnp.float32).astype(bf16)
    dq, dk = (0.01 * jax.random.normal(k, (BATCH, TOKENS, VALUE_HEADS * WIDTH), jnp.float32).astype(bf16)
              for k in ks[4:6])
    dv, dout = (0.01 * jax.random.normal(k, (BATCH, TOKENS, values), jnp.float32).astype(bf16) for k in ks[6:8])
    serves = VALUE_HEADS // KEY_HEADS

    def unit(t):
        t32 = t.astype(jnp.float32)
        return t32 * jax.lax.rsqrt(jnp.sum(jnp.square(t32), axis=-1, keepdims=True) + 1e-6)

    def xla_before(qkvz, taps):
        qkv = jax.nn.silu(L.causal_conv(qkvz[..., :2 * keys + values], taps))
        q = (unit(qkv[..., :keys].reshape(BATCH, TOKENS, KEY_HEADS, WIDTH)) * WIDTH ** -0.5).astype(bf16)
        k = unit(qkv[..., keys:2 * keys].reshape(BATCH, TOKENS, KEY_HEADS, WIDTH)).astype(bf16)
        return q.reshape(BATCH, TOKENS, keys), k.reshape(BATCH, TOKENS, keys), qkv[..., 2 * keys:]

    def xla_after(o, qkvz, scale):
        z = qkvz[..., 2 * keys + values:].reshape(BATCH, TOKENS, VALUE_HEADS, WIDTH)
        n = rms_norm(o.reshape(z.shape).astype(jnp.float32), scale, EPS)
        return (n * jax.nn.silu(z.astype(jnp.float32))).astype(bf16).reshape(o.shape)

    def key_heads(x):  # a value head's share each -> a key head's sum, as the core's backward hands it on
        x = x.astype(jnp.float32).reshape(BATCH, TOKENS, KEY_HEADS, serves, WIDTH)
        return jnp.sum(x, axis=3).reshape(BATCH, TOKENS, keys).astype(bf16)

    def xla_before_bwd(qkvz, taps, dq, dk, dv):
        return jax.vjp(xla_before, qkvz, taps)[1]((key_heads(dq), key_heads(dk), dv))

    def xla_after_bwd(o, qkvz, scale, dout):
        return jax.vjp(xla_after, o, qkvz, scale)[1](dout)

    def rel(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    out = {"device": jax.devices()[0].device_kind, "settings": []}
    want_before = jax.jit(xla_before)(qkvz, taps)
    want_dx, want_dtaps = jax.jit(xla_before_bwd)(qkvz, taps, dq, dk, dv)
    want_after = jax.jit(xla_after)(o, qkvz, scale)
    want_do, want_dz, want_dscale = jax.jit(xla_after_bwd)(o, qkvz, scale, dout)
    out["xla_ms"] = {
        "before_fwd": timed(jax.jit(xla_before), qkvz, taps),
        "before_bwd_alone": timed(jax.jit(xla_before_bwd), qkvz, taps, dq, dk, dv),
        "after_fwd": timed(jax.jit(xla_after), o, qkvz, scale),
        "after_bwd_alone": timed(jax.jit(xla_after_bwd), o, qkvz, scale, dout)}
    print("xla", json.dumps(out["xla_ms"]), flush=True)
    # the least the bytes allow: each operand once
    n = TOKENS * 2  # bytes a channel of a row of the batch
    out["hbm_floor_ms"] = {
        "conv_norm_fwd": 2 * n * (2 * keys + values) / HBM * 1e3,
        "conv_norm_bwd": n * (2 * (2 * keys + values) + 3 * values) / HBM * 1e3,
        "gated_norm_fwd": 3 * n * values / HBM * 1e3, "gated_norm_bwd": 5 * n * values / HBM * 1e3}
    settings = [tuple(int(x) for x in a.split(",")) for a in argv] or [(L._TOKENS, L._LANES, L._AT_ONCE)]
    for tokens, lanes, at_once in settings:
        L._TOKENS, L._LANES, L._AT_ONCE = tokens, lanes, at_once
        row = {"tokens": tokens, "lanes": lanes, "at_once": at_once}
        try:
            before = jax.jit(lambda a, b: L._conv_norm(heads, a, b))
            after = jax.jit(lambda *a: L._gated_norm(heads, EPS, *a))
            after_bwd = jax.jit(lambda *a: L._gated_norm_bwd(heads, EPS, *a))

            @jax.jit
            def both_bwd(o, qkvz, scale, dout, taps, dq, dk, dv):
                """The two backwards as the rule chains them: the second fills
                the first's array (a jit's own argument would be copied first)."""
                into, do, dscale = L._gated_norm_bwd(heads, EPS, o, qkvz, scale, dout)
                return L._conv_norm_bwd(heads, qkvz, taps, dq, dk, dv, into) + (do, dscale)

            row["ms"] = {"conv_norm_fwd": timed(before, qkvz, taps),
                         "gated_norm_fwd": timed(after, o, qkvz, scale),
                         "gated_norm_bwd": timed(after_bwd, o, qkvz, scale, dout),
                         "both_bwd": timed(both_bwd, o, qkvz, scale, dout, taps, dq, dk, dv)}
            row["ms"]["conv_norm_bwd"] = row["ms"]["both_bwd"] - row["ms"]["gated_norm_bwd"]
            got_dx, got_dtaps, got_do, got_dscale = both_bwd(o, qkvz, scale, dout, taps, dq, dk, dv)
            cut = 2 * keys + values
            row["rel_err_to_xla"] = {
                "q k v": [rel(g, w) for g, w in zip(before(qkvz, taps), want_before)],
                "dqkv": rel(got_dx[..., :cut], want_dx[..., :cut]), "dtaps": rel(got_dtaps, want_dtaps),
                "gated": rel(after(o, qkvz, scale), want_after), "do": rel(got_do, want_do),
                "dz": rel(got_dx[..., cut:], want_dz[..., cut:]), "dscale": rel(got_dscale, want_dscale)}
        except Exception as e:  # a setting the compiler refuses
            row["error"] = str(e)[:400]
        out["settings"].append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "linear_passes_sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python3
"""Chip measurement behind the sizes of the passes around the delta rules'
cores (ops/linear_attention.py: `conv_norm_fwd` / `conv_norm_bwd`,
`gated_norm_fwd` / `gated_norm_bwd`, and the per-channel rule's `kda_gate_fwd`
/ `kda_gate_bwd`), at the two cells' widths (8192 tokens, 4 taps, bf16; four
rows of the batch a call and the time a row, so that a call's dispatch, a
fifth of a millisecond and more, weighs a quarter):

    qwen3next  16 key heads serving 32 value heads of 128, `Wqkvz`'s [q | k | v
               | z] (8192 convolved channels), SiLU(z)
    kimi       32 heads of 128, `Wqkv`'s [q | k | v] (12288 convolved
               channels), z an array of its own behind a sigmoid, and the
               gate's pass over (tokens, 4096) float32

Not a benchmark cell: run by hand through the chip tool,

    chiprun -- python3 scripts/linear_passes_sweep.py [qwen3next | kimi] [tokens,lanes,at_once ...]

and read `chiprun_out/linear_passes_sweep.<mixer>.json` (no mixer named: both,
one after the other). Times are medians of fenced calls on one chip, a pass
alone (a call of each of its segments), for each setting of (`_TOKENS`,
`_LANES`, `_AT_ONCE`) given, the committed one first; beside them the XLA form
of the same arithmetic (models/parts/linear.linear_mixer's and parts/kda.kda_mixer's), forward
and backward, how far each kernel's results lie from it, and the least the
bytes allow. Refuses to run where jax finds no TPU.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH, TOKENS, WIDTH, TAPS, EPS = 4, 8192, 128, 4, 1e-6
MIXERS = {"qwen3next": ("linear_layout", 16, 32), "kimi": ("kda_layout", 32, 32)}  # the layout's maker, key heads, value heads
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


def sweep(mixer, settings):
    """One mixer's passes at each setting -> the record written for it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.ops import linear_attention as L
    from galvatron_tpu.ops.norms import rms_norm

    maker, key_heads, value_heads = MIXERS[mixer]
    heads = L.Heads(key_heads, WIDTH, value_heads, WIDTH)
    keys, values, serves = key_heads * WIDTH, value_heads * WIDTH, value_heads // key_heads
    cut = 2 * keys + values  # where q, k, v end
    committed = getattr(L, maker)(heads)
    inside = committed.z.start > 0  # z in the projection's output, or an array of its own
    per_channel = "kda_gate" in committed.counted  # the gate's pass
    ks = jax.random.split(jax.random.PRNGKey(0), 13)
    bf16 = jnp.bfloat16

    def normal(key, width, scale=1.0):
        return (scale * jax.random.normal(key, (BATCH, TOKENS, width), jnp.float32)).astype(bf16)

    x = normal(ks[0], cut + values * inside)
    within = x if inside else normal(ks[8], values)
    taps = jax.random.uniform(ks[1], (cut, TAPS), jnp.float32, -0.5, 0.5)
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (WIDTH,), jnp.float32)
    o = normal(ks[3], values)
    dq, dk = (normal(k, value_heads * WIDTH, 0.01) for k in ks[4:6])  # a value head's share each
    dv, dout = (normal(k, values, 0.01) for k in ks[6:8])
    f, dt_bias = normal(ks[9], keys), jax.random.normal(ks[10], (keys,), jnp.float32)
    a_log = jnp.log(jax.random.uniform(ks[11], (key_heads,), jnp.float32, 0.05, 4.0))
    dg = 0.01 * jax.random.normal(ks[12], (BATCH, TOKENS, keys), jnp.float32)

    def unit(t):
        t32 = t.astype(jnp.float32)
        return t32 * jax.lax.rsqrt(jnp.sum(jnp.square(t32), axis=-1, keepdims=True) + 1e-6)

    def xla_before(x, taps):
        qkv = jax.nn.silu(L.causal_conv(x[..., :cut], taps))
        q = (unit(qkv[..., :keys].reshape(BATCH, TOKENS, key_heads, WIDTH)) * WIDTH ** -0.5).astype(bf16)
        k = unit(qkv[..., keys:2 * keys].reshape(BATCH, TOKENS, key_heads, WIDTH)).astype(bf16)
        return q.reshape(BATCH, TOKENS, keys), k.reshape(BATCH, TOKENS, keys), qkv[..., 2 * keys:]

    def xla_after(o, within, scale):
        z = within[..., -values:].reshape(BATCH, TOKENS, value_heads, WIDTH).astype(jnp.float32)
        n = rms_norm(o.reshape(z.shape).astype(jnp.float32), scale, EPS)
        return (n * getattr(jax.nn, committed.gate)(z)).astype(bf16).reshape(o.shape)

    def xla_gate(f, dt_bias, a_log):
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f.astype(jnp.float32) + dt_bias).reshape(
            BATCH, TOKENS, key_heads, WIDTH)
        return g.reshape(f.shape)

    def to_key_heads(x):  # a value head's share each -> a key head's sum, as the core's backward hands it on
        x = x.astype(jnp.float32).reshape(BATCH, TOKENS, key_heads, serves, WIDTH)
        return jnp.sum(x, axis=3).reshape(BATCH, TOKENS, keys).astype(bf16)

    def xla_before_bwd(x, taps, dq, dk, dv):
        return jax.vjp(xla_before, x, taps)[1]((to_key_heads(dq), to_key_heads(dk), dv))

    def xla_after_bwd(o, within, scale, dout):
        return jax.vjp(xla_after, o, within, scale)[1](dout)

    def xla_gate_bwd(f, dt_bias, a_log, dg):
        return jax.vjp(xla_gate, f, dt_bias, a_log)[1](dg)

    def rel(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    out = {"device": jax.devices()[0].device_kind, "mixer": mixer, "settings": []}
    want_before = jax.jit(xla_before)(x, taps)
    want_dx, want_dtaps = jax.jit(xla_before_bwd)(x, taps, dq, dk, dv)
    want_after = jax.jit(xla_after)(o, within, scale)
    want_do, want_dz, want_dscale = jax.jit(xla_after_bwd)(o, within, scale, dout)
    out["xla_ms"] = {
        "before_fwd": timed(jax.jit(xla_before), x, taps),
        "before_bwd_alone": timed(jax.jit(xla_before_bwd), x, taps, dq, dk, dv),
        "after_fwd": timed(jax.jit(xla_after), o, within, scale),
        "after_bwd_alone": timed(jax.jit(xla_after_bwd), o, within, scale, dout)}
    # the least the bytes allow: each operand once
    n = TOKENS * 2  # bytes a bf16 channel of a row of the batch
    out["hbm_floor_ms"] = {
        "conv_norm_fwd": 2 * n * cut / HBM * 1e3,
        "conv_norm_bwd": n * (2 * cut + 3 * values) / HBM * 1e3,
        "gated_norm_fwd": 3 * n * values / HBM * 1e3, "gated_norm_bwd": 5 * n * values / HBM * 1e3}
    if per_channel:
        want_g = jax.jit(xla_gate)(f, dt_bias, a_log)
        want_gate_bwd = jax.jit(xla_gate_bwd)(f, dt_bias, a_log, dg)
        out["xla_ms"].update(gate_fwd=timed(jax.jit(xla_gate), f, dt_bias, a_log),
                             gate_bwd_alone=timed(jax.jit(xla_gate_bwd), f, dt_bias, a_log, dg))
        out["hbm_floor_ms"].update(kda_gate_fwd=3 * n * keys / HBM * 1e3,  # f bf16, g float32
                                   kda_gate_bwd=4 * n * keys / HBM * 1e3)  # f, dg float32, df
    print(mixer, "xla", json.dumps(out["xla_ms"]), flush=True)
    for tokens, lanes, at_once in settings or [(L._TOKENS, L._LANES, L._AT_ONCE)]:
        L._TOKENS, L._LANES, L._AT_ONCE = tokens, lanes, at_once
        layout = getattr(L, maker)(heads)  # its blocks of lanes follow `_LANES`
        row = {"tokens": tokens, "lanes": lanes, "at_once": at_once}
        try:
            before = jax.jit(lambda a, b: L._conv_norm(layout.qkv, a, b))
            after = jax.jit(lambda *a: L._gated_norm(layout, EPS, *a))
            after_bwd = jax.jit(lambda *a: L._gated_norm_bwd(layout, EPS, *a))

            @jax.jit
            def both_bwd(o, within, scale, dout, x, taps, dq, dk, dv):
                """The two backwards as the rule chains them: where z lies in
                the projection's output the second fills the first's array (a
                jit's own argument would be copied first)."""
                dwithin, do, dscale = L._gated_norm_bwd(layout, EPS, o, within, scale, dout)
                dx, dtaps = L._conv_norm_bwd(layout.qkv, x, taps, (dq, dk, dv), dwithin if inside else None)
                return dx, dtaps, (dx if inside else dwithin)[..., -values:], do, dscale

            row["ms"] = {"conv_norm_fwd": timed(before, x, taps),
                         "gated_norm_fwd": timed(after, o, within, scale),
                         "gated_norm_bwd": timed(after_bwd, o, within, scale, dout),
                         "both_bwd": timed(both_bwd, o, within, scale, dout, x, taps, dq, dk, dv)}
            row["ms"]["conv_norm_bwd"] = row["ms"]["both_bwd"] - row["ms"]["gated_norm_bwd"]
            got_dx, got_dtaps, got_dz, got_do, got_dscale = both_bwd(o, within, scale, dout, x, taps, dq, dk, dv)
            row["rel_err_to_xla"] = {
                "q k v": [rel(g, w) for g, w in zip(before(x, taps), want_before)],
                "dqkv": rel(got_dx[..., :cut], want_dx[..., :cut]), "dtaps": rel(got_dtaps, want_dtaps),
                "gated": rel(after(o, within, scale), want_after), "do": rel(got_do, want_do),
                "dz": rel(got_dz, want_dz[..., -values:]),
                "dscale": rel(got_dscale, want_dscale)}
            if per_channel:
                gate = jax.jit(lambda *a: L._channel_gate(layout, *a))
                gate_bwd = jax.jit(lambda *a: L._channel_gate_bwd(layout, *a))
                row["ms"].update(kda_gate_fwd=timed(gate, f, dt_bias, a_log),
                                 kda_gate_bwd=timed(gate_bwd, f, dt_bias, a_log, dg))
                row["rel_err_to_xla"].update(
                    g=rel(gate(f, dt_bias, a_log), want_g),
                    **{name: rel(got, want) for name, got, want in zip(
                        ("df", "ddt_bias", "da_log"), gate_bwd(f, dt_bias, a_log, dg), want_gate_bwd)})
        except Exception as e:  # a setting the compiler refuses
            row["error"] = str(e)[:400]
        out["settings"].append(row)
        print(mixer, json.dumps(row), flush=True)
    return out


def main(argv) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("linear_passes_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    mixers = [a for a in argv if a in MIXERS] or list(MIXERS)
    settings = [tuple(int(x) for x in a.split(",")) for a in argv if a not in MIXERS]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for mixer in mixers:
        with open(os.path.join(ROOT, "chiprun_out", "linear_passes_sweep.%s.json" % mixer), "w") as f:
            json.dump(sweep(mixer, settings), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

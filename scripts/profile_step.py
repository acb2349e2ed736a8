"""Step-time breakdown on the real TPU chip (VERDICT r3 item 1).

Times the bench.py train-step's components so the MFU work targets the real
bottleneck. Every synced call pays a host dispatch, so each measurement runs
the op K times inside one jit (lax.scan) and DIFFERENCES two iteration counts
(K2 - K1): the dispatch cancels and the per-iteration device time remains
(same differencing idea as bench.py's layer-count differencing; reference
model_profiler.py:328-372).

Usage (on the chip, through the tool): python scripts/profile_step.py [--quick]
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
import optax

from galvatron_tpu.models import base as M

HIDDEN, FFN, HEADS, SEQ = 4096, 11008, 32, 2048
LAYERS, BATCH = 2, 4
K1, K2 = 4, 8


def cfg_():
    return M.TransformerConfig(
        hidden_size=HIDDEN, num_heads=HEADS, num_layers=LAYERS,
        ffn_hidden=FFN, vocab_size=256, max_seq_len=SEQ,
        norm_type="rmsnorm", activation="swiglu", position_type="rope",
        qkv_bias=False, mlp_bias=False, out_bias=False,
        compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
    )


def sync(x):
    return jax.block_until_ready(x)


def timeit(fn, *args, iters=4, warmup=2):
    for _ in range(warmup):
        sync(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        sync(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.min(ts))


def scanned(body, carry_init, k):
    """jit a K-iteration scan of body so dispatch amortises; body must return
    a same-shaped carry that DEPENDS on the previous one (no dead-code elim)."""

    @jax.jit
    def run(c):
        out, _ = jax.lax.scan(lambda cc, _: (body(cc), ()), c, None, length=k)
        return out

    return lambda: run(carry_init)


def diffed(body, carry_init, iters=4, label=""):
    """Difference K2 vs K1 iteration scans; print the result immediately so a
    failure later in the run does not lose earlier numbers."""
    t1 = timeit(scanned(body, carry_init, K1), iters=iters)
    t2 = timeit(scanned(body, carry_init, K2), iters=iters)
    t = (t2 - t1) / (K2 - K1)
    if label:
        print("measured %-10s: %8.2f ms" % (label, t * 1e3), flush=True)
    return t


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    iters = 2 if args.quick else 4

    cfg = cfg_()
    key = jax.random.PRNGKey(0)
    layers = [M.init_layer_params(k, cfg) for k in jax.random.split(key, LAYERS)]
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, HIDDEN), jnp.bfloat16)
    positions = jnp.broadcast_to(jnp.arange(SEQ), (BATCH, SEQ))
    tx = optax.adam(1e-4)
    opt_state = tx.init(layers)

    def loss_fn(layers, x):
        y = x
        for lp in layers:
            y = M.layer_forward(lp, y, positions, cfg)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    # ---- full step, K iterations inside one jit (params/opt as scan carry)
    def step_body(carry):
        layers, opt_state = carry
        loss, grads = jax.value_and_grad(loss_fn)(layers, x)
        updates, opt_state = tx.update(grads, opt_state, layers)
        return optax.apply_updates(layers, updates), opt_state

    t_step = diffed(step_body, (layers, opt_state), iters=iters, label="step")

    # ---- forward only (carry = x so iterations chain)
    def fwd_body(xx):
        y = xx
        for lp in layers:
            y = M.layer_forward(lp, y, positions, cfg)
        return 0.5 * xx + 0.5 * y

    t_fwd = diffed(fwd_body, x, iters=iters, label="fwd")

    # ---- forward + backward (carry = params, nudged by grads)
    def fb_body(ls):
        g = jax.grad(loss_fn)(ls, x)
        return jax.tree.map(lambda p, gg: p - 1e-6 * gg, ls, g)

    t_fb = diffed(fb_body, layers, iters=iters, label="fwd+bwd")

    # ---- adam update only
    grads = jax.jit(jax.grad(loss_fn))(layers, x)
    sync(grads)

    def adam_body(carry):
        ls, st = carry
        updates, st = tx.update(grads, st, ls)
        return optax.apply_updates(ls, updates), st

    t_adam = diffed(adam_body, (layers, opt_state), iters=iters, label="adam")

    # ---- attention isolated
    from galvatron_tpu.ops.attention import core_attention

    q = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, HEADS, 128), jnp.bfloat16)

    def attn_f_body(c):
        return 0.5 * c + 0.5 * core_attention(c, c, c, causal=True)

    def attn_loss(c):
        return jnp.mean(core_attention(c, c, c, causal=True).astype(jnp.float32) ** 2)

    def attn_fb_body(c):
        return c - 1e-6 * jax.grad(attn_loss)(c)

    t_attn_f = diffed(attn_f_body, q, iters=iters, label="attn-fwd")
    t_attn_fb = diffed(attn_fb_body, q, iters=iters, label="attn-f+b")

    # ---- big matmul ceiling
    w1 = jax.random.normal(jax.random.PRNGKey(3), (HIDDEN, FFN), jnp.bfloat16)
    a = x.reshape(-1, HIDDEN)

    def mm_body(c):
        return 0.99 * c + 1e-6 * ((c @ w1) @ w1.T)

    t_mm = diffed(mm_body, a, iters=iters, label="mm-pair")
    mm_flops = 2 * 2 * a.shape[0] * HIDDEN * FFN

    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(layers))
    tokens = BATCH * SEQ
    flops_step = 6.0 * n_params * tokens + 12 * LAYERS * SEQ * HIDDEN * tokens * 0.5
    peak = 197e12
    attn_flops = 4 * BATCH * HEADS * SEQ * SEQ * 128 * 0.5
    print("device:", jax.devices()[0].device_kind)
    print("params: %.1fM  tokens/step: %d  (all times dispatch-free)" % (n_params / 1e6, tokens))
    print("full step : %7.2f ms   (MFU %.3f)" % (t_step * 1e3, flops_step / t_step / peak))
    print("fwd only  : %7.2f ms   (MFU %.3f)" % (t_fwd * 1e3, flops_step / 3 / t_fwd / peak))
    print("fwd+bwd   : %7.2f ms   (MFU %.3f)" % (t_fb * 1e3, flops_step / t_fb / peak))
    print("bwd alone : %7.2f ms   (ideal %.2f)" % ((t_fb - t_fwd) * 1e3, flops_step * 2 / 3 / peak * 1e3))
    print("adam only : %7.2f ms" % (t_adam * 1e3))
    print("attn fwd  : %7.2f ms   (%.0f%% of kernel peak)" % (t_attn_f * 1e3, 100 * attn_flops / t_attn_f / peak))
    print("attn f+b  : %7.2f ms   (%.0f%% of kernel peak)" % (t_attn_fb * 1e3, 100 * 3 * attn_flops / t_attn_fb / peak))
    print("attn bwd  : %7.2f ms   (ideal %.2f)" % ((t_attn_fb - t_attn_f) * 1e3, 2 * attn_flops / peak * 1e3))
    print("mm pair   : %7.2f ms   (%.0f%% peak)" % (t_mm * 1e3, 100 * mm_flops / t_mm / peak))


if __name__ == "__main__":
    main()

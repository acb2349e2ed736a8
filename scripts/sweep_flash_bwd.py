"""Sweep pallas flash-attention BACKWARD block sizes on the real chip
(VERDICT r3 item 1 / r4 item 2: the forward was swept in round 3; the
backward keeps the forward's blocks until this records a winner). Times
jax.grad through the kernel with K iterations inside one jitted scan so the
host dispatch amortises.

Wedge-tolerant (a Mosaic compile of an odd block shape can hang): the parent
stays off jax, every config runs in a fresh subprocess — one process on the
chip at a time — with a hard timeout, and results stream to
scripts/flash_bwd_sweep_results.json after each config, so a wedge mid-sweep
keeps everything measured so far.

Usage (on the chip, through the tool): python scripts/sweep_flash_bwd.py
"""

import itertools
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from _bench_util import (  # noqa: E402
    child_pythonpath,
    interpret_ctx_factory,
    run_isolated,
)

SMOKE = bool(os.environ.get("GALVATRON_SWEEP_SMOKE"))
BATCH, SEQ, HEADS, HD = (1, 256, 2, 128) if SMOKE else (4, 2048, 32, 128)
K = 1 if SMOKE else 8
RESULTS_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "flash_bwd_sweep_results%s.json" % ("_smoke" if SMOKE else ""),
)
CONFIG_TIMEOUT_S = 240.0


def bwd_time(block_overrides):
    """fwd+bwd time per call with the given dkv/dq block sizes (ms)."""
    import jax
    import jax.numpy as jnp

    from galvatron_tpu.ops import attention as A
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    orig = A._flash_block_sizes

    def patched(sq, sk):
        bq = A._flash_divisor(sq, 1024)
        bk = A._flash_divisor(sk, 512)
        kw = dict(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
        )
        kw.update({k: A._flash_divisor(sq if "q" in k.split("_")[1] else sk, v)
                   for k, v in block_overrides.items()})
        return BlockSizes(**kw)

    # native on TPU; interpret mode for the off-chip smoke path
    ctx = interpret_ctx_factory()()

    A._flash_block_sizes = patched
    try:
        q = jax.random.normal(jax.random.PRNGKey(2), (BATCH, SEQ, HEADS, HD), jnp.bfloat16)

        def attn_loss(c):
            return jnp.mean(A.core_attention(c, c, c, causal=True).astype(jnp.float32) ** 2)

        @jax.jit
        def run(c):
            def body(cc, _):
                return cc - 1e-6 * jax.grad(attn_loss)(cc), ()

            out, _ = jax.lax.scan(body, c, None, length=K)
            return out

        def sync(x):
            return float(jnp.sum(x.astype(jnp.float32)))

        with ctx:
            sync(run(q))
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                sync(run(q))
                ts.append(time.perf_counter() - t0)
        return float(np.min(ts)) / K * 1e3
    finally:
        A._flash_block_sizes = orig


def _grid():
    def ov(bq, bk):
        return {
            "block_q_major_dkv": bq, "block_q_dkv": bq,
            "block_k_major_dkv": bk, "block_k_dkv": bk,
            "block_q_dq": bq, "block_k_major_dq": bk, "block_k_dq": bk,
        }

    configs = [("base_1024_512", {})]
    if SMOKE:
        # machinery check only: one override config (interpret mode is slow)
        return configs + [("q256_k256", ov(256, 256))]
    for bq, bk in itertools.product([256, 512, 1024], [256, 512, 1024]):
        if bq == 1024 and bk == 512:
            continue
        configs.append(("q%d_k%d" % (bq, bk), ov(bq, bk)))
    return configs


def main():
    if os.environ.get("GALVATRON_SWEEP_CONFIG"):
        name = os.environ["GALVATRON_SWEEP_CONFIG"]
        overrides = dict(_grid())[name]
        ms = bwd_time(overrides)
        import jax

        print(json.dumps({"name": name, "ms": ms,
                          "device": jax.devices()[0].device_kind}))
        return

    context = {"shapes": dict(batch=BATCH, seq=SEQ, heads=HEADS, hd=HD),
               "steps_per_call": K}
    results = {}
    if os.path.exists(RESULTS_PATH):
        try:
            prev = json.load(open(RESULTS_PATH))
            # only resume measurements taken under the SAME shapes/K: stale
            # entries from other conditions must not compete for "best"
            if all(prev.get(k) == v for k, v in context.items()):
                results = prev.get("results", {})
                print("resuming; already have %d results" % len(results), flush=True)
            else:
                print("results file is from different shapes/K; starting fresh",
                      flush=True)
        except (json.JSONDecodeError, OSError) as e:
            print("results file unreadable (%s); starting fresh" % e, flush=True)
    for name, _ in _grid():
        if name in results:
            continue
        env = dict(os.environ, GALVATRON_SWEEP_CONFIG=name)
        env["PYTHONPATH"] = child_pythonpath(env, _REPO)
        # shared wedge-tolerant harness: own process group (killed as a
        # unit on timeout), JSON kept even if the child died in teardown
        payload, rc, err_tail = run_isolated(
            [sys.executable, os.path.abspath(__file__)], env, CONFIG_TIMEOUT_S,
        )
        if payload is None:
            if rc is None:
                print("%s: TIMEOUT" % name, flush=True)
            else:
                print("%s: FAIL rc=%s %s" % (name, rc, err_tail[-120:]), flush=True)
            continue
        results[name] = payload["ms"]
        print("%s: %.2f ms (device %s)" % (name, results[name],
                                           payload.get("device", "?")), flush=True)
        best = min(results, key=results.get)
        # atomic write: a kill mid-dump must not corrupt the resume file
        tmp = RESULTS_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(dict(context, device=payload.get("device"),
                           results=results, best=best), f, indent=1)
        os.replace(tmp, RESULTS_PATH)
    if results:
        best = min(results, key=results.get)
        print("BEST: %s = %.2f ms (baseline %s)"
              % (best, results[best], results.get("base_1024_512")))
    else:
        print("no results — every config failed or timed out")


if __name__ == "__main__":
    main()

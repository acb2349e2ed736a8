#!/usr/bin/env python3
"""Laguna-XS.2 at its published widths and the timed sizes on the chip,
program against plain reference, outside any timed window (the
`model-configs` guide's section 3, item 3):

    chiprun -- python3 scripts/laguna_chip_check.py [--seeds N,N,...]

One seeded 8192-token sequence a seed through the benchmark's own
configuration (benchmarks/configs/laguna-xs.2-d5-e32-v8.json: full attention +
dense MLP, window attention + experts three times, full attention + experts; 32
of 256 experts held, 1/8 of the vocabulary) and the cell's own layout (one
chip, `--checkpoint 1`, scanned runs) against the float32 reference
(benchmarks/references/laguna_lm.py) on the same weights and batches. A seed
reads:

- **every layer's mixer**: `MIXERS[...].forward` (bf16 compute as the cell
  runs it: the flash kernels on the full layers, the window kernels on the
  window layers) on the normed activations the program hands it, against the
  reference's `attention` (explicit band mask on explicit float32 logits,
  yarn written out, the per-head gate) on the same input: the relative error
  of the output, the worst layer of each type;
- the router's own arithmetic against numpy's float64 on the rows it was
  given, a routed block (handed out of the very program whose gradients are
  compared, by a `jax.debug.callback` around the router), and the share of
  tokens whose pick differs from the float32 reference's in any block;
- the loss, and every leaf's gradient twice: against the reference as it
  routes itself and against the reference HELD TO THE PROGRAM'S ROUTING
  (`forced_experts`);
- **the loss of three consecutive steps** of the program's own train step
  (AdamW at 3e-4, so that the weights move) against the reference's loss on the
  weights and the batch each step was given.

**Breaks, on the first seed, each of which must FAIL `mixer_out`** (the
program's mixers against a reference with one piece of the mathematics
changed): the window off by one either way (511, 513 keys), the window layers
turned with the full layers' rope, the per-head gate left out, yarn's scale on
cos and sin left out. **A control in the next lower precision, on the first
seed, which must FAIL at least one limit**: the router's logits from bf16
operands (rounded with `jax.lax.reduce_precision`: a cast there and back the
TPU compiler takes out), measured as the program's router is. **The band is
paid for as a band**: the window kernels alone at the cell's shapes, forward +
backward, at 16384 tokens take twice their 8192 time to within 10 % (a kernel
that walked the causal triangle would take four times). Writes
`chiprun_out/laguna_chip_check.json`; its LAST line of output is the verdict
with each measure's largest reading over the seeds beside its limit; exits 1
unless the program passes on every seed and every break and the control fail.
Refuses to run where jax finds no TPU.

Why two gradient comparisons: scripts/olmoe_chip_check.py's docstring.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "laguna-c1-s8k"
# measure -> most allowed, for the program as the cell runs it (bf16 compute).
# Two readings each (my chip run, PR 49, call 3: seeds 32, 7, 2024; the breaks and the control on seed 32):
# the largest the program gave over the seeds and all five layers, and the break's / the control's.
#   loss                       3.7e-4   bf16 router 7.5e-5  (the cell's nine runs on other seeds: 1.9e-5 to 4.3e-4)
#   loss_three_steps           2.8e-4   (nine steps: 3e-5 to 2.8e-4; AdamW at 3e-4 moves the loss by 1e-2 a step)
#   router                     9.8e-8   bf16 router 1.66e-3
#   mixer_out_window           5.65e-3  a window of 511 keys 1.506e-2, of 513 keys 1.498e-2, the full layers' rope 0.469,
#                                       no gate 0.531 (5.26e-3 to 5.65e-3 on all nine layer x seed: the bf16 roundings of q, k,
#                                       v, the probabilities and the output, no function of the weights; ONE key of 512 at
#                                       nearly uniform probabilities moves a row by 1.5e-2, 2.7 x the roundings: the two
#                                       readings are that close, and the limit lies between them with 1.6 x on either side)
#   mixer_out_full             6.44e-3  yarn's scale left out 0.141, no gate 0.531 (layer 0 6.42e-3 to 6.44e-3, layer 4
#                                       5.29e-3 to 5.42e-3)
#   tokens_flipped_share       0.338    (any of 4 blocks x 8 picks of 256; a block 0.079 to 0.130, growing with depth; 0.094 across the held 32)
#   worst_leaf_same_routing    0.0531   (a router kernel on every seed; the median leaf 0.018)
#   worst_leaf                 0.148    (the last block's router kernel: its gradient comes through the 32 held experts alone)
#   the band's time            forward + backward at 16384 over 8192 tokens 1.874 (9.16 -> 17.17 ms; the band's FLOPs 2.03 x,
#                                       the grid's steps 2 x; a kernel that walked the triangle 4 x)
# `router` 100 x over the one reading and 1 / 166 of the other: it tells a bf16 router from a float32 one and NOTHING else
# here does (the control's loss gap is inside every seed's own). `loss` and `loss_three_steps` are the cell's own
# `reference_loss.abs` (5.4 x the largest gap seen); the three without a control about 1.5 times the program's largest.
LIMITS = {"loss": 2e-3, "loss_three_steps": 2e-3, "router": 1e-5, "mixer_out_full": 0.02, "mixer_out_window": 0.009,
          "tokens_flipped_share": 0.50, "worst_leaf_same_routing": 0.08, "worst_leaf": 0.22}
BAND_RATIO = (1.8, 2.2)  # twice, to within 10 %
BREAKS = {"window_511": (("sliding_window", 511), ()), "window_513": (("sliding_window", 513), ()),
          "window_layers_with_the_full_layers_rope": (None, ("window_rope",)),
          "gate_left_out": (None, ("head_gate",)), "yarn_scale_left_out": (None, ("yarn_scale",))}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="32,7,2024", help="comma-separated; the breaks and the control run on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("laguna_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import MIXERS
    from galvatron_tpu.models.parts.common import _norm
    from galvatron_tpu.models.parts.embed_head import embed_tokens
    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import moe
    from galvatron_tpu.runtime import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    cell = cells.load_cell(ROOT, CELL)
    ref = cells.load_module(ROOT, "benchmarks/references/%s.py" % cell.config["reference"])
    build = cells.import_attr(cell.config["program"]["config_fn"])
    seq, rows = cell.traffic["seq_length"], cell.traffic["global_batch"]
    cfg = build(cell.config["program"]["preset"],
                **{**cell.fields, "max_seq_len": seq, "compute_dtype": jnp.bfloat16})
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=rows, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp)
    k = cfg.experts_per_token
    kinds = cfg.layer_kinds()
    windowed = [kind.startswith("window") for kind in kinds]
    committed_router = moe.router_logits
    positions = jnp.broadcast_to(jnp.arange(seq), (rows, seq))
    rel = lambda got, want: float(np.linalg.norm(np.asarray(got, np.float64) - np.asarray(want, np.float64))  # noqa: E731
                                  / np.linalg.norm(np.asarray(want, np.float64)))

    def reference_loss(p, given):
        parts = ref.loss_parts(p, given, fields)
        return parts["loss"], parts

    reference_grad = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))  # traced twice: free, forced
    reference_only = jax.jit(lambda p, given: ref.loss(p, given, fields))

    def router_bf16(y, kernel):
        """The router's logits from bf16 operands, the product accumulated in float32."""
        to_bf16 = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)  # noqa: E731
        return jnp.dot(to_bf16(y), to_bf16(kernel), precision=jax.lax.Precision.HIGHEST)

    @jax.jit
    def mixers_in_and_out(params, tokens):
        """Every layer's normed input as the program hands it to the mixer and
        what the mixer makes of it, in the layers' order: one unrolled forward."""
        x = embed_tokens(params["embed"], tokens, positions, cfg)
        handed = []
        for lp, kind in zip(params["layers"], kinds):
            lcfg = cfg.layer_config(kind)
            y = _norm(x, lp["ln1"], lcfg)
            o = MIXERS[lcfg.mixer].forward(lp, y, positions, lcfg, mesh=None, axes=None, attn_bias=None,
                                           attn_sharding=None, return_kv=False)[0]
            handed.append((y, o))
            out = M.layer_forward(lp, x, positions, lcfg)
            x = out[0] if lcfg.layer_aux else out
        return handed

    def reference_mixer(lp, y, is_window, changed=None, off=()):
        """The reference's mixer on the program's input, float32, a sequence at a time."""
        given = dict(fields) if changed is None else {**fields, changed[0]: changed[1]}
        lp32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda lp32, y: jax.lax.map(
                lambda row: ref.attention(lp32, row[0], row[1], given, is_window, frozenset(off)),
                (y.astype(jnp.float32), positions)))(lp32, y)

    def mixer_errors(params, tokens, with_breaks):
        """{layer: rel error}, and with the breaks {break: the worst layer's rel error among those it touches}."""
        handed = mixers_in_and_out(params, tokens)
        errors, broken = {}, {name: 0.0 for name in BREAKS} if with_breaks else {}
        for layer, ((y, o), lp, is_window) in enumerate(zip(handed, params["layers"], windowed)):
            errors[layer] = rel(o, reference_mixer(lp, y, is_window))
            for name, (changed, off) in BREAKS.items() if with_breaks else ():
                touches = not is_window if name == "yarn_scale_left_out" else (is_window or name == "gate_left_out")
                if touches:  # the largest over the layers the break touches: each must fail
                    read = rel(o, reference_mixer(lp, y, is_window, changed, off))
                    broken[name] = read if not broken[name] else min(broken[name], read)
        return errors, broken

    def timed(fn, *operands, repeat=5):
        jax.block_until_ready(fn(*operands))
        took = []
        for _ in range(repeat):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            took.append(time.perf_counter() - t)
        return statistics.median(took) * 1e3

    def band_times():
        """The window kernels alone, forward + backward, at the cell's tokens and at twice them."""
        lcfg = cfg.layer_config("window.routed")
        both = jax.jit(jax.grad(lambda q, k_, v: jnp.sum(A.core_attention(
            q, k_, v, window=cfg.sliding_window).astype(jnp.float32) ** 2), (0, 1, 2)))
        out = {}
        for tokens in (seq, 2 * seq):
            ks = jax.random.split(jax.random.PRNGKey(3), 3)
            q, k_, v = (jax.random.normal(key, (rows, tokens, heads, cfg.head_dim), jnp.bfloat16)
                        for key, heads in zip(ks, (lcfg.num_heads, cfg.num_kv_heads, cfg.num_kv_heads)))
            out[tokens] = timed(both, q, k_, v)
        return out

    def one_seed(seed, first):
        params = model.init_params(jax.random.PRNGKey(seed))
        draw = lambda key: jax.random.randint(jax.random.PRNGKey(key), (rows, seq), 0, cfg.vocab_size)  # noqa: E731

        def batch_of(tokens):
            return model.shard_batch(dict(
                tokens=tokens, positions=positions, labels=jnp.roll(tokens, -1, 1),
                loss_mask=jnp.ones((rows, seq), jnp.float32).at[:, -1].set(0.0)))

        tokens = draw(seed + 1)
        batch = batch_of(tokens)
        routers = [lp["router"] for lp in params["layers"] if "router" in lp]

        def picks_of(seen):
            """(routed blocks, tokens, k) as the program picks: the k largest probabilities."""
            return jnp.stack([jax.lax.top_k(jax.nn.softmax(jnp.asarray(logits), axis=-1), k)[1] for _, logits in seen])

        def program(router=committed_router):
            """The cell's own loss (scanned runs, recomputation) and gradients,
            and what each block's router was given and made of it IN THAT VERY
            PROGRAM: [(y, logits)] in the blocks' order (a block is known by
            its router kernel's first entry)."""
            handed = {}

            def keep(tag, y, logits):
                handed.setdefault(float(tag), (np.asarray(y.astype(jnp.float32)), np.asarray(logits)))

            def spy(y, kernel):
                logits = router(y, kernel)
                jax.debug.callback(keep, kernel[0, 0], y, logits)
                return logits

            moe.router_logits = spy
            try:
                (total, parts), grads = jax.jit(jax.value_and_grad(
                    model.loss_parts_fn, has_aux=True))(params, batch)
                grads = jax.device_get(grads)
                jax.effects_barrier()
            finally:
                moe.router_logits = committed_router
            parts = {"loss": float(total), "ce": float(parts["loss_ce"]),
                     "expert_rows_held_over_even": float(parts["expert_rows_held_over_even"]),
                     "expert_load_max_over_mean": float(parts["expert_load_max_over_mean"])}
            seen = [handed[float(router["kernel"][0, 0])] for router in routers]
            return parts, grads, seen

        def reference(forced=None):
            """(parts, gradients, picks); `forced` (routed blocks, tokens, k) holds it to a routing."""
            given = dict(batch)
            if forced is not None:
                given["forced_experts"] = forced.reshape(len(routers), rows, seq, k).transpose(1, 0, 2, 3)
            (_, parts), grads = reference_grad(params, given)
            picks = parts.pop("picks").transpose(1, 0, 2, 3).reshape(len(routers), rows * seq, k)
            return {name: float(v) for name, v in parts.items()}, jax.device_get(grads), picks

        def as_sets(picks):
            return np.asarray(jnp.sum(jax.nn.one_hot(picks, cfg.num_experts, dtype=jnp.int8), axis=-2))  # (blocks, T, E)

        def leaf_errors(got, want):
            want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
            errors = {}
            for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
                r, g = np.asarray(want[path], np.float64), np.asarray(g, np.float64)
                norm = np.linalg.norm(r)
                errors[jax.tree_util.keystr(path)] = float(np.linalg.norm(g - r) / norm) if norm else float(
                    np.linalg.norm(g))
            return errors

        def router_error(seen):
            """Worst block: rms of (logits - float64 product) over rms of the product."""
            worst = 0.0
            for (y, logits), router in zip(seen, routers):
                exact = np.asarray(y, np.float64) @ np.asarray(router["kernel"], np.float64)
                worst = max(worst, float(np.sqrt(np.mean((np.asarray(logits, np.float64) - exact) ** 2)
                                                 / np.mean(exact ** 2))))
            return worst

        def three_steps():
            """The program's own train step three times from this seed's weights
            (which the step takes over: nothing reads them afterwards), a new
            batch a step; before each the reference's loss on the weights and
            batch the step is given. -> the largest gap, and the losses."""
            tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=3e-4, warmup_steps=0, total_steps=100))
            p, step = params, model.make_train_step(tx)
            opt = model.init_opt_state(tx, p)
            pairs = []
            for i in range(3):
                given = batch_of(draw(seed + 100 + i))
                want = float(reference_only(p, given))
                p, opt, metrics = step(p, opt, given)
                pairs.append((float(metrics["loss"]), want))
            return max(abs(got - want) for got, want in pairs), pairs

        out = {"seed": seed}
        layer_errors, broken = mixer_errors(params, tokens, first)
        out["mixer_out_a_layer"] = layer_errors
        ref_parts, ref_grads, ref_picks = reference()
        ref_sets = as_sets(ref_picks)
        out["reference"] = ref_parts
        held_first, held = cfg.held_experts
        parts, grads, seen = program()
        picks = picks_of(seen)
        differs = np.any(as_sets(picks) != ref_sets, axis=-1)  # (blocks, T)
        crosses = np.any((as_sets(picks) != ref_sets)[..., held_first:held_first + held], axis=-1)
        free = leaf_errors(grads, ref_grads)
        same = leaf_errors(grads, reference(forced=picks)[1])
        del grads, ref_grads
        router_read, low = router_error(seen), None  # on the very rows it was given
        if first:
            low_parts, _, low_seen = program(router_bf16)
            low = {"router": router_error(low_seen), "loss": abs(low_parts["loss"] - ref_parts["loss"])}
        steps_gap, steps = three_steps()  # last: the step takes the weights over
        measured = {
            "loss": abs(parts["loss"] - ref_parts["loss"]),
            "loss_three_steps": steps_gap,
            "router": router_read,
            "mixer_out_full": max(e for e, w in zip(layer_errors.values(), windowed) if not w),
            "mixer_out_window": max(e for e, w in zip(layer_errors.values(), windowed) if w),
            "tokens_flipped_share": float(np.mean(np.any(differs, axis=0))),
            "worst_leaf_same_routing": max(same.values()),
            "worst_leaf": max(free.values()),
        }
        out["program"] = {
            **parts, "reference_loss": ref_parts["loss"], "three_steps_program_and_reference": steps,
            "picks_flipped_share_a_block": [float(v) for v in np.mean(differs, axis=1)],
            "tokens_flipped_across_the_held_set_share": float(np.mean(np.any(crosses, axis=0))),
            "measured": measured,
            "outside_limits": {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]},
            "worst_leaf_name": max(free, key=free.get),
            "worst_leaf_same_routing_name": max(same, key=same.get),
            "median_leaf_same_routing": float(np.median(list(same.values()))),
            "leaves_against_the_reference_as_it_routes": free,
            "leaves_against_the_reference_held_to_this_routing": same,
        }
        verdicts = {"program": not out["program"]["outside_limits"]}
        print("seed %d" % seed, "program", "PASS" if verdicts["program"] else "FAIL", json.dumps(
            {n: v for n, v in out["program"].items() if not n.startswith("leaves")}),
            "mixers", json.dumps(layer_errors), flush=True)
        if first:
            limit = lambda name: LIMITS["mixer_out_full" if name == "yarn_scale_left_out" else "mixer_out_window"]  # noqa: E731
            out["breaks"] = {name: {"mixer_out_least_over_the_layers_it_touches": read, "limit": limit(name),
                                    "fails": read > limit(name)} for name, read in broken.items()}
            for name, row in out["breaks"].items():
                verdicts["break_" + name] = not row["fails"]
                print("seed %d" % seed, "break", name, "FAILS as it must" if row["fails"] else "PASSES: a fault",
                      json.dumps(row), flush=True)
            outside = {n: [v, LIMITS[n]] for n, v in low.items() if v > LIMITS[n]}
            out["control_bf16_router"] = {"measured": low, "outside_limits": outside}
            verdicts["control_bf16_router"] = not outside
            print("seed %d" % seed, "control_bf16_router", "PASS: a fault" if not outside else "FAILS as it must",
                  json.dumps(out["control_bf16_router"]), flush=True)
        return out, verdicts

    band = band_times()
    ratio = band[2 * seq] / band[seq]
    band_ok = BAND_RATIO[0] <= ratio <= BAND_RATIO[1]
    print("band", json.dumps({"fwd_bwd_ms": band, "ratio": ratio, "limits": BAND_RATIO, "ok": band_ok}), flush=True)
    runs, sound, must_fail = [], True, {}
    for i, seed in enumerate(seeds):
        out, verdicts = one_seed(seed, first=i == 0)
        runs.append(out)
        sound = sound and verdicts.pop("program")
        must_fail.update({name: not passed for name, passed in verdicts.items()})
    largest = {n: max(r["program"]["measured"][n] for r in runs) for n in LIMITS}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "laguna_chip_check.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "tokens": rows * seq, "limits": LIMITS,
                   "window_kernels_fwd_bwd_ms": band, "window_16k_over_8k": ratio,
                   "largest_over_seeds": largest, "runs": runs}, f, indent=1)
    ok = sound and band_ok and all(must_fail.values())
    print("VERDICT %s: the program within its limits on seeds %s: %s; the window kernels at 16384 over 8192 tokens: "
          "%.3f (a band: %s); each break and the control outside a limit: %s; largest reading [limit]: %s; "
          "the breaks: %s; the bf16-router control: %s" % (
              "PASS" if ok else "FAIL", seeds, sound, ratio, band_ok, json.dumps(must_fail),
              json.dumps({n: [largest[n], LIMITS[n]] for n in LIMITS}), json.dumps(runs[0]["breaks"]),
              json.dumps(runs[0]["control_bf16_router"]["measured"])), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

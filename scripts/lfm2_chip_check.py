#!/usr/bin/env python3
"""LFM2-MoE at its published widths and the timed sizes on the chip, program
against plain reference, outside any timed window (the `model-configs` guide's
section 3, item 3):

    chiprun -- python3 scripts/lfm2_chip_check.py [--seeds N,N,...]

Two seeded 8192-token sequences a seed through the benchmark's own
configuration (benchmarks/configs/lfm2-8b-a1b-d5-e8-v4.json: conv + dense MLP,
attention + experts, three conv + experts; 8 of 32 experts held, 1/4 of the
vocabulary) and the cell's own layout (one chip, `--checkpoint 1`, scanned
runs) against the float32 reference on the same weights and batches. A seed
reads:

- **the gated short convolution, EVERY conv layer**: the layer's mixer
  (`models/parts/conv.conv_mixer`, bf16 compute as the cell runs it) on the
  normed activations the program hands it, against the LITERAL convolution in
  FLOAT64 ON THE HOST (numpy: `y W_in`, `B * u`, three shifted multiply-adds,
  `C * v`, `W_out`, on the bf16-rounded operands the program multiplies): the
  relative error of the output over all rows, and **over the rows 1024 n, 1024 n
  + 1 and 1024 n + 2 each alone** (where XLA:TPU's shift within 1024-row tiles
  showed: PERF.md section 6, PR 42), and of a probe's gradients: the worst of
  the mixer's three leaves and its input, and the input's gradient over the rows
  1024 n - 2 and 1024 n - 1 alone (the transposed taps reach the other way);
- the router's own arithmetic against numpy's float64 on the rows it was
  given, a routed block (handed out of the very program whose gradients are
  compared, by a `jax.debug.callback` around the router), and the share of
  tokens whose pick differs from the float32 reference's in any block;
- every leaf's gradient twice, against the reference as it routes itself and
  against the reference HELD TO THE PROGRAM'S ROUTING (`forced_experts`);
- **the loss of three consecutive steps** of the program's own train step
  (AdamW at 3e-4, so that the weights move) against the reference's loss on the
  weights and the batch each step was given.

**A control that breaks the mixer, on the first seed, which must FAIL**: layer
0's mixer with the tap that reaches furthest back dropped, against the literal
convolution with all three. **A control in the next lower precision, on the
first seed, which must FAIL at least one limit**: the router's logits from
bf16 operands (rounded with `jax.lax.reduce_precision`: a cast there and back
the TPU compiler takes out), measured as the program's router is. Writes
`chiprun_out/lfm2_chip_check.json`; its LAST line of output is the verdict with
each measure's largest reading over the seeds beside its limit; exits 1 unless
the program passes on every seed and both controls fail. Refuses to run where
jax finds no TPU.

Why two gradient comparisons: scripts/olmoe_chip_check.py's docstring.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "lfm2moe-c1-s8k"
# measure -> most allowed, for the program as the cell runs it (bf16 compute).
# Two readings each (my chip run, PR 46, call 1: seeds 32, 7, 2024; the controls
# on seed 32): the largest the program gave over the seeds and all four conv
# layers, and the control's.
#   loss                       5.5e-4   bf16 router 6.2e-5  (the cell's twelve runs on other seeds: its `reference_loss.why`)
#   loss_three_steps           5.0e-4   (nine steps: 3.0e-5 to 5.0e-4; AdamW at 3e-4 moves the loss by 1e-2 a step)
#   router                     1.37e-7  bf16 router 1.66e-3
#   conv_out                   4.39e-3  dropped tap 0.579   (4.38e-3 to 4.39e-3 on all twelve layer x seed: the bf16 roundings of
#                                       [B C u], B u, v, C v and the output, no function of the weights)
#   conv_rows_1024n            4.98e-3  dropped tap 0.583   (rows 0, 1, 2 and 1024 n, 1024 n + 1, 1024 n + 2 each alone: 4.1e-3 to
#                                       5.0e-3, the spread of 14 rows' statistics around 4.39e-3: NO row differs, where a shift
#                                       inside 1024-row tiles reads 0.5 to 0.9; under the dropped tap rows 0 and 1 stay exact,
#                                       they never see that tap, and row 2 reads 0.54)
#   conv_worst_leaf            4.39e-3  dropped tap 0.579   (`wout` on every layer; `win` 3.99e-3, the taps 3.97e-3 to 4.18e-3, dy 4.32e-3)
#   conv_dy_rows_before_1024n  4.39e-3  dropped tap 0.587
#   tokens_flipped_share       0.199    (any of 4 blocks x 4 picks of 32; a block 0.035 to 0.090, growing with depth; 0.093 across the held 8)
#   worst_leaf_same_routing    0.0955   (a router kernel on every seed; the median leaf 0.029)
#   worst_leaf                 0.299    (the last block's router kernel: its gradient comes through the 8 held experts alone)
# Each conv limit lies between its two readings, 4.6 to 6.8 x over the one and 1 / 19 to 1 / 29 of the other;
# `router` 73 x over the one and 1 / 166 of the other: it tells a bf16 router from a float32 one and NOTHING else
# here does (the control's loss gap, 6.2e-5, is inside every seed's own). `loss` and `loss_three_steps` are the
# cell's own `reference_loss.abs` (3.6 x the largest gap seen); the three without a control about 1.4 to 1.5
# times the program's largest.
LIMITS = {"loss": 2e-3, "loss_three_steps": 2e-3, "router": 1e-5, "conv_out": 0.02, "conv_rows_1024n": 0.03,
          "conv_worst_leaf": 0.03, "conv_dy_rows_before_1024n": 0.03, "tokens_flipped_share": 0.30,
          "worst_leaf_same_routing": 0.14, "worst_leaf": 0.42}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="32,7,2024", help="comma-separated; the controls run on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("lfm2_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.common import _norm
    from galvatron_tpu.models.parts.conv import conv_mixer
    from galvatron_tpu.models.parts.embed_head import embed_tokens
    from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
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
    k, hidden = cfg.experts_per_token, cfg.hidden_size
    kinds = cfg.layer_kinds()
    conv_layers = [i for i, kind in enumerate(kinds) if kind.startswith("conv")]
    committed_router = moe.router_logits
    positions = jnp.broadcast_to(jnp.arange(seq), (rows, seq))
    tile = 1024  # XLA:TPU's tile of rows, where a shift inside it would show
    starts = np.arange(tile, seq, tile)

    def reference_loss(p, given):
        parts = ref.loss_parts(p, given, fields)
        return parts["loss"], parts

    reference_grad = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))  # traced twice: free, forced
    reference_only = jax.jit(lambda p, given: ref.loss(p, given, fields))

    def router_bf16(y, kernel):
        """The router's logits from bf16 operands, the product accumulated in float32."""
        to_bf16 = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)  # noqa: E731
        return jnp.dot(to_bf16(y), to_bf16(kernel), precision=jax.lax.Precision.HIGHEST)

    rel = lambda got, want: float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))  # noqa: E731

    @jax.jit
    def mixer_inputs(params, tokens):
        """Every conv layer's normed input as the program hands it, in the
        layers' order: one unrolled forward through the stack."""
        x = embed_tokens(params["embed"], tokens, positions, cfg)
        handed = []
        for lp, kind in zip(params["layers"], kinds):
            lcfg = cfg.layer_config(kind)
            if kind.startswith("conv"):
                handed.append(_norm(x, lp["ln1"], lcfg))
            out = M.layer_forward(lp, x, positions, lcfg)
            x = out[0] if lcfg.layer_aux else out
        return handed

    def literal_float64(conv, y, probe):
        """HF `Lfm2MoeShortConv` in float64 on the host, on the operands the
        program multiplies (its input and kernels rounded to bf16, as `_dense`
        casts them; the taps float32): the output of one sequence (S, h), and
        of `sum(out * probe)` the gradients of the three leaves and the input."""
        bf16 = lambda t: np.asarray(jnp.asarray(t).astype(jnp.bfloat16).astype(jnp.float32), np.float64)  # noqa: E731
        y, win, wout = bf16(y), bf16(conv["win"]["kernel"]), bf16(conv["wout"]["kernel"])
        taps, probe = np.asarray(conv["taps"], np.float64), np.asarray(probe, np.float64)
        s, taps_n = y.shape[0], taps.shape[1]
        gate_in, gate_out, u = np.split(y @ win, 3, axis=-1)
        padded = np.zeros((s + taps_n - 1, hidden))
        padded[taps_n - 1:] = gate_in * u
        v = sum(taps[:, j] * padded[j:j + s] for j in range(taps_n))
        gated = gate_out * v
        out = gated @ wout
        d_gated = probe @ wout.T
        d_v = d_gated * gate_out
        d_padded = np.zeros_like(padded)
        for j in range(taps_n):
            d_padded[j:j + s] += taps[:, j] * d_v
        d_bu = d_padded[taps_n - 1:]
        d_bcu = np.concatenate([d_bu * u, d_gated * v, d_bu * gate_in], axis=-1)
        grads = {"win": y.T @ d_bcu, "wout": gated.T @ probe,
                 "taps": np.stack([np.sum(d_v * padded[j:j + s], axis=0) for j in range(taps_n)], axis=1),
                 "y": d_bcu @ win.T}
        return out, grads

    def conv_errors(params, tokens, with_control):
        """Every conv layer's mixer on the program's own normed activations
        against the float64 literal convolution, a sequence of the batch at a
        time on the host; the first sequence's rows by name."""
        out_rows = {}
        for layer, y in zip(conv_layers, mixer_inputs(params, tokens)):
            lcfg, lp = cfg.layer_config(kinds[layer]), params["layers"][layer]
            probe = jax.random.normal(jax.random.PRNGKey(17), y.shape, jnp.float32)

            def run(taps_dropped=0):
                def of(conv, y):
                    conv = dict(conv, taps=conv["taps"].at[:, :taps_dropped].set(0.0))
                    out = conv_mixer({"conv": conv}, y, None, lcfg)[0]
                    return jnp.sum(out.astype(jnp.float32) * probe), out

                (_, out), grads = jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))(lp["conv"], y)
                return jax.device_get((out, grads))

            want_out, want = zip(*(literal_float64(lp["conv"], y[b], probe[b]) for b in range(rows)))
            want_out = np.stack(want_out)
            want_leaf = {name: sum(w[name] for w in want) for name in ("win", "wout", "taps")}
            want_dy = np.stack([w["y"] for w in want])

            def against_the_literal(out, grads):
                conv_grads, dy = grads
                leaves = {"win": rel(conv_grads["win"]["kernel"], want_leaf["win"]),
                          "wout": rel(conv_grads["wout"]["kernel"], want_leaf["wout"]),
                          "taps": rel(conv_grads["taps"], want_leaf["taps"]), "y": rel(dy, want_dy)}
                at = {"row_%s" % name: rel(out[:, starts + off], want_out[:, starts + off])
                      for name, off in (("1024n", 0), ("1024n+1", 1), ("1024n+2", 2))}
                first = {"first_row_%d" % t: rel(out[:, t], want_out[:, t]) for t in range(3)}
                before = {"dy_row_1024n%d" % off: rel(dy[:, starts + off], want_dy[:, starts + off])
                          for off in (-2, -1)}
                return {"conv_out": rel(out, want_out), "conv_rows_1024n": max({**at, **first}.values()),
                        "conv_worst_leaf": max(leaves.values()), "conv_dy_rows_before_1024n": max(before.values()),
                        "leaves": leaves, "rows": {**first, **at, **before}}

            out_rows[layer] = {"program": against_the_literal(*run())}
            if with_control and layer == conv_layers[0]:
                out_rows[layer]["control_dropped_tap"] = against_the_literal(*run(taps_dropped=1))
        return out_rows

    def one_seed(seed, with_control):
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
            """(routed blocks, tokens, k) as the program picks: the k largest of score + bias."""
            return jnp.stack([jax.lax.top_k(jax.nn.sigmoid(jnp.asarray(logits)) + router[ROUTER_BIAS], k)[1]
                              for (_, logits), router in zip(seen, routers)])

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

        out = {"seed": seed, "conv": conv_errors(params, tokens, with_control)}
        ref_parts, ref_grads, ref_picks = reference()
        ref_sets = as_sets(ref_picks)
        out["reference"] = ref_parts
        first, held = cfg.held_experts
        parts, grads, seen = program()
        picks = picks_of(seen)
        differs = np.any(as_sets(picks) != ref_sets, axis=-1)  # (blocks, T)
        crosses = np.any((as_sets(picks) != ref_sets)[..., first:first + held], axis=-1)
        free = leaf_errors(grads, ref_grads)
        same = leaf_errors(grads, reference(forced=picks)[1])
        del grads, ref_grads
        worst = lambda name: max(row["program"][name] for row in out["conv"].values())  # noqa: E731
        router_read, low = router_error(seen), None  # on the very rows it was given
        if with_control:
            low_parts, _, low_seen = program(router_bf16)
            low = {"router": router_error(low_seen), "loss": abs(low_parts["loss"] - ref_parts["loss"])}
        steps_gap, steps = three_steps()  # last: the step takes the weights over
        measured = {
            "loss": abs(parts["loss"] - ref_parts["loss"]),
            "loss_three_steps": steps_gap,
            "router": router_read,
            "conv_out": worst("conv_out"), "conv_rows_1024n": worst("conv_rows_1024n"),
            "conv_worst_leaf": worst("conv_worst_leaf"),
            "conv_dy_rows_before_1024n": worst("conv_dy_rows_before_1024n"),
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
            "conv", json.dumps(out["conv"]), flush=True)
        if with_control:
            names = ("conv_out", "conv_rows_1024n", "conv_worst_leaf", "conv_dy_rows_before_1024n")
            measured = {n: out["conv"][conv_layers[0]]["control_dropped_tap"][n] for n in names}
            outside = {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]}
            out["control_dropped_tap"] = {"measured": measured, "outside_limits": outside}
            verdicts["control_dropped_tap"] = not outside
            print("seed %d" % seed, "control_dropped_tap", "PASS" if not outside else "FAIL",
                  json.dumps(out["control_dropped_tap"]), flush=True)
            outside = {n: [v, LIMITS[n]] for n, v in low.items() if v > LIMITS[n]}
            out["control_bf16_router"] = {"measured": low, "outside_limits": outside}
            verdicts["control_bf16_router"] = not outside
            print("seed %d" % seed, "control_bf16_router", "PASS" if not outside else "FAIL",
                  json.dumps(out["control_bf16_router"]), flush=True)
        return out, verdicts

    runs, sound, controls_fail = [], True, {"control_dropped_tap": False, "control_bf16_router": False}
    for i, seed in enumerate(seeds):
        out, verdicts = one_seed(seed, with_control=i == 0)
        runs.append(out)
        sound = sound and verdicts["program"]
        controls_fail = {name: failed or not verdicts.get(name, True) for name, failed in controls_fail.items()}
    largest = {n: max(r["program"]["measured"][n] for r in runs) for n in LIMITS}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "lfm2_chip_check.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "tokens": rows * seq, "limits": LIMITS,
                   "largest_over_seeds": largest, "runs": runs}, f, indent=1)
    ok = sound and all(controls_fail.values())
    print("VERDICT %s: the program within its limits on seeds %s: %s; the controls outside: %s; "
          "largest reading [limit]: %s; the dropped-tap control: %s; the bf16-router control: %s" % (
              "PASS" if ok else "FAIL", seeds, sound, json.dumps(controls_fail),
              json.dumps({n: [largest[n], LIMITS[n]] for n in LIMITS}),
              json.dumps(runs[0]["control_dropped_tap"]["measured"]),
              json.dumps(runs[0]["control_bf16_router"]["measured"])), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

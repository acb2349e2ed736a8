#!/usr/bin/env python3
"""Kimi-Linear at its published widths and the timed sizes on the chip, program
against plain reference, outside any timed window (the `model-configs` guide's
section 3, item 3):

    chiprun -- python3 scripts/kimilin_chip_check.py [--seeds N,N,...]

One seeded 8192-token sequence a seed through the benchmark's own configuration
(benchmarks/configs/kimi-linear-48b-a3b-d5-e8-v8.json: KDA + dense MLP, two KDA
+ experts, MLA + experts, KDA + experts; 8 of 256 experts held, 1/8 of the
vocabulary) and the cell's own layout (one chip, `--checkpoint 1`, scanned
runs) against the float32 reference on the same weights and batch. A seed
reads:

- the loss;
- the router's own arithmetic against numpy's float64 on the rows it was
  given, a routed block (handed out of the very program whose gradients are
  compared, by a `jax.debug.callback` around the router), and the share of
  tokens whose pick differs from the float32 reference's in any block;
- **the per-channel delta rule, EVERY KDA layer**: the layer's q, k, v, g, beta
  as the mixer's XLA form makes them (bf16 operands, the float32 gate a head
  and channel; `mixer_form` held to "xla" for that one forward through the
  stack: the kernel form hands its core flat arrays inside one rule), through
  `ops/linear_attention.kda_rule` and through the reference's token-by-token
  recurrence in float32 on the chip (`kda_recurrence`): the relative error of
  `o` over the whole sequence and over the LAST 64 tokens, where 8192 tokens of
  carried state have piled up, and of the final states themselves, these
  against the same recurrence in FLOAT64 ON THE HOST (`final_states_float64`:
  the float32 recurrence on the chip is itself off where a channel forgets
  least, its `exp` reading low 8192 times in a row: PERF.md, PR 36);
- **the passes around the core** (PR 44: `conv_norm_*`, `kda_gate_*`,
  `gated_norm_*`: the convolution, SiLU, the L2 norms, the per-channel gate's
  softplus and the gated RMSNorm x sigmoid as Pallas passes): layer 0's
  whole mixer through them against the XLA form of the same
  arithmetic (`mixer_form` held to "xla"; the core is the kernels' in both) on
  the same weights and the same normed activations: the relative error of the
  mixer's output and, of a probe's gradient, the worst leaf's (the mixer's
  twelve leaves and its input);
- every leaf's gradient twice, against the reference as it routes itself and
  against the reference HELD TO THE PROGRAM'S ROUTING (`forced_experts`).

**A control that breaks the passes, on the first seed, which must FAIL**: the
same mixer through the kernels with the convolution's first tap dropped (a
three-tap convolution), against the XLA form with all four.

**A control in the next lower precision, on the first seed, which must FAIL at
least one limit**: layer 0's rule, in its XLA form (the program's own is the
kernels', `kda_fwd`: their state never leaves VMEM), with its carried state rounded to bf16 after
every chunk (`_carry` replaced by one that rounds with
`jax.lax.reduce_precision`: a cast there and back the TPU compiler takes out;
compiled as a program of its own). Writes `chiprun_out/kimilin_chip_check.json`;
its LAST line of output is the verdict with each measure's largest reading over
the seeds beside its limit; exits 1 unless the program passes on every seed and
both controls fail. Refuses to run where jax finds no TPU.

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
CELL = "kimilin-c1-s8k"
# measure -> most allowed, for the program as the cell runs it (bf16 compute).
# Two readings each (my chip runs, PR 42, call 65: seeds 32, 7, 2024; the
# control on seed 32): the largest the program gave over the seeds and all four
# KDA layers, and the control's.
#   loss                     5.8e-4   (the cell's six runs on other seeds 4.3e-5 to 3.6e-4)
#   router                   1.02e-7
#   core_state               5.0e-6   bf16 state 2.2e-3   (against float64 on the host; the float32 recurrence
#                                     token by token ON THE CHIP is itself 6e-5 to 3.6e-4 off)
#   core_o                   3.04e-3  bf16 state 3.17e-3  (bf16 operands on the way to the output: 2^-9)
#   core_o_last_chunk        3.05e-3  bf16 state 3.17e-3
#   tokens_flipped_share     0.506    (any of 4 blocks x 8 picks of 256; 0.044 across the held 8)
#   worst_leaf_same_routing  0.160    (the last block's router kernel; its experts' kernels 0.10; median leaf 0.031)
#   worst_leaf               0.319    (a router kernel: its gradient comes through the 8 held experts alone)
# `core_state` tells a bf16 state from a float32 one by 440 times and its limit
# lies between the readings, 20 x over the one and 1 / 22 of the other. The
# control moves neither the output (bf16 operands on the way to it already) nor
# the loss or a gradient further than the bf16 stream they read does, so the
# other limits cannot lie between two readings: `loss` is the cell's own
# `reference_loss.abs` (3.4 x the largest gap seen), `router` float32's own
# rounding with room, the rest about 1.4 times the program's largest.
# Since PR 43 the rule's core runs as the kernels `kda_fwd` / `kda_bwd` (my chip
# runs, PR 43, the same seeds): `core_state` 2.8e-6 (every exponent a sum of
# g's, no difference of running sums: nearer float64 than the XLA form's 5.0e-6),
# `core_o` 3.47e-3 and `core_o_last_chunk` 3.73e-3 (the kernels round `q . E`
# and `k . E` to bf16 at every level of the halving, the XLA form kept its 16 x
# 16 diagonal blocks float32: 2^-9 twice where it was once; twelve readings from
# 3.42e-3 to 3.73e-3); the limits are as they were, the control the XLA form's.
# Since PR 44 what lies between the projections and the core runs as Pallas
# passes, and layer 0's mixer through them is held to its XLA form (my chip
# run, PR 44, call 2, the same seeds; the control on seed 32):
#   passes_out               7.17e-3  dropped tap 0.762   (7.02e-3 to 7.17e-3 over the seeds: both forms in bf16)
#   passes_worst_leaf        8.26e-3  dropped tap 0.883   (`wf_a`'s kernel on every seed; the twelve leaves 6.8e-3 to 8.3e-3)
# each limit between its two readings: 4.2 x and 6.1 x the program's largest,
# 1 / 25 and 1 / 18 of the control's (the Qwen3-Next check's limits for the same
# passes, which read 7.1e-3 and 9.8e-3 there). The eight older limits did not
# move: `core_state` 2.84e-6, `core_o` 3.47e-3, `core_o_last_chunk` 3.73e-3,
# `loss` 4.4e-4, `worst_leaf_same_routing` 0.186, `worst_leaf` 0.354.
# (The first version of the REFERENCE read every leaf 15 % off and the loss up
# to 1.9e-3 off: XLA:TPU shifted its convolution within 1024-row tiles; PERF.md
# section 6, PR 42. These limits would have caught it: 0.35 and 0.52 then.)
LIMITS = {"loss": 2e-3, "router": 1e-5, "core_state": 1e-4, "core_o": 4.3e-3, "core_o_last_chunk": 4.3e-3,
          "tokens_flipped_share": 0.70, "worst_leaf_same_routing": 0.22, "worst_leaf": 0.45,
          "passes_out": 0.03, "passes_worst_leaf": 0.05}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="32,7,2024", help="comma-separated; the control runs on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("kimilin_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.common import _norm
    from galvatron_tpu.models.parts.embed_head import embed_tokens
    from galvatron_tpu.models.parts import kda as part
    from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
    from galvatron_tpu.ops import linear_attention as L
    from galvatron_tpu.ops import moe
    from galvatron_tpu.runtime import construct_hybrid_parallel_model

    cell = cells.load_cell(ROOT, CELL)
    ref = cells.load_module(ROOT, "benchmarks/references/%s.py" % cell.config["reference"])
    build = cells.import_attr(cell.config["program"]["config_fn"])
    seq = cell.traffic["seq_length"]
    cfg = build(cell.config["program"]["preset"],
                **{**cell.fields, "max_seq_len": seq, "compute_dtype": jnp.bfloat16})
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=1, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp)
    k = cfg.experts_per_token
    kinds = cfg.layer_kinds()
    kda_layers = [i for i, kind in enumerate(kinds) if kind.startswith("kda")]
    committed_router, committed_rule, committed_carry = moe.router_logits, part.kda_rule, L._carry
    committed_form = part.mixer_form

    def xla_form(*_, **__):
        return "xla"

    def reference_loss(p, given):
        parts = ref.loss_parts(p, given, fields)
        return parts["loss"], parts

    reference_grad = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))  # traced twice: free, forced

    def carry_bf16(m, b):
        """`_carry` with the state rounded to bf16 after every chunk."""
        def step(state, mb):
            new = L._mm(mb[0], state) + mb[1]
            return jax.lax.reduce_precision(new, exponent_bits=8, mantissa_bits=7), state

        last, starts = jax.lax.scan(step, jnp.zeros_like(b[0]), (m, b))
        return starts, last

    def rule_with_bf16_state(*operands):
        # under a new function object: `jax.checkpoint` keeps a function's trace
        head_core = L._head_core
        L._carry, L._head_core = carry_bf16, lambda *a: head_core(*a)
        try:
            return L.kda_rule(*operands, impl="xla")  # the form whose carry can be rounded from outside
        finally:
            L._carry, L._head_core = committed_carry, head_core

    @jax.jit
    def rule_operands(params, tokens):
        """Every KDA layer's (q, k, v, g, beta) as the mixer's XLA form makes
        them (the form that hands the core its operands), in the layers'
        order: one unrolled forward through the stack."""
        x = embed_tokens(params["embed"], tokens, jnp.arange(seq)[None], cfg)
        handed = []

        def spy(*operands, **where):
            handed.append(operands)
            return committed_rule(*operands, **where)

        part.kda_rule, part.mixer_form = spy, xla_form
        try:
            for lp, kind in zip(params["layers"], kinds):
                x = M.layer_forward(lp, x, jnp.arange(seq)[None], cfg.layer_config(kind))[0]
        finally:
            part.kda_rule, part.mixer_form = committed_rule, committed_form
        return handed

    def mixer_errors(params, tokens, with_control):
        """Layer 0's mixer (the projections, the passes, the core)
        on the normed activations the program hands it, through the passes'
        kernels and through the XLA form, each a program of its own: the
        output, and every leaf's gradient of a fixed probe of it."""
        lcfg, lp = cfg.layer_config(kinds[0]), params["layers"][0]  # the stack's first layer is a KDA layer
        y = jax.jit(lambda: _norm(embed_tokens(params["embed"], tokens, jnp.arange(seq)[None], cfg),
                                    lp["ln1"], lcfg))()
        probe = jax.random.normal(jax.random.PRNGKey(17), y.shape, jnp.float32)

        def run(form, taps_dropped=0):
            def of(kda, y):
                kda = dict(kda, conv=kda["conv"].at[:, :taps_dropped].set(0.0))
                part.mixer_form = form
                try:
                    out = part.kda_mixer({"kda": kda}, y, None, lcfg)[0]
                finally:
                    part.mixer_form = committed_form
                return jnp.sum(out.astype(jnp.float32) * probe), out

            fn = jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))
            if form is committed_form:
                text = fn.lower(lp["kda"], y).as_text()
                assert all(name in text for name in ("conv_norm_fwd", "kda_gate_bwd", "gated_norm_bwd")), (
                    "on the chip the passes' form is the kernels'")
            (_, out), grads = fn(lp["kda"], y)
            return jax.device_get((out, grads))

        rel = lambda g, r: float(np.linalg.norm(np.asarray(g, np.float64) - np.asarray(r, np.float64))  # noqa: E731
                                 / np.linalg.norm(np.asarray(r, np.float64)))
        want_out, want_grads = run(xla_form)

        def against_the_xla_form(out, grads):
            leaves = {jax.tree_util.keystr(path): rel(g, r) for (path, g), r in zip(
                jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree_util.tree_leaves(want_grads))}
            return {"passes_out": rel(out, want_out), "passes_worst_leaf": max(leaves.values()),
                    "passes_worst_leaf_name": max(leaves, key=leaves.get), "leaves": leaves}

        errors = {"program": against_the_xla_form(*run(committed_form))}
        if with_control:
            errors["control_dropped_tap"] = against_the_xla_form(*run(committed_form, taps_dropped=1))
        return errors

    @jax.jit
    def recurrence(q, kk, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return ref.kda_recurrence(*(t[0].astype(jnp.float32) for t in (q, kk, v, g, beta)))

    def final_states_float64(q, kk, v, g, beta):
        """The recurrence's final states in float64 on the host (numpy): what
        the states are held to (the chip's float32 `exp` reads low, and 8192
        factors `exp(g_t)` a channel carry that into the float32 recurrence's
        state, which is the reference's error and not the program's)."""
        q, kk, v, g, beta = (np.asarray(t[0].astype(jnp.float32), np.float64) for t in (q, kk, v, g, beta))
        state = np.zeros((v.shape[1], kk.shape[2], v.shape[2]))
        for t in range(v.shape[0]):
            state *= np.exp(g[t])[:, :, None]
            u = beta[t][:, None] * (v[t] - np.matmul(kk[t][:, None, :], state)[:, 0, :])
            state += kk[t][:, :, None] * u[:, None, :]
        return state

    def core_errors(params, tokens, with_control):
        """Every KDA layer's rule on the operands the program makes for it: the
        output against the recurrence token by token on the chip, the final
        states against the same recurrence in float64 on the host."""
        rel = lambda d, e: float(np.linalg.norm(d) / np.linalg.norm(e))  # noqa: E731
        rows = {}
        for layer, operands in zip(kda_layers, rule_operands(params, tokens)):
            exact, state_on_chip = recurrence(*operands)
            exact_state = final_states_float64(*operands)

            def error(rule):
                o, state = jax.jit(rule)(*operands)
                diff = o[0].astype(jnp.float32) - exact
                return (rel(diff, exact), rel(diff[-L.CHUNK:], exact[-L.CHUNK:]),
                        rel(np.asarray(state[0], np.float64) - exact_state, exact_state))

            rows[layer] = {"program": error(committed_rule),
                           "recurrence_float32_on_chip_state": rel(
                               np.asarray(state_on_chip, np.float64) - exact_state, exact_state),
                           "decay_mean": float(jnp.mean(jnp.exp(operands[3]))),
                           "gate_min": float(jnp.min(operands[3])),
                           "state_abs_max": float(np.max(np.abs(exact_state))),
                           "o_rms": float(jnp.sqrt(jnp.mean(exact * exact)))}
            if with_control and layer == kda_layers[0]:
                rows[layer]["control_bf16_state"] = error(rule_with_bf16_state)
        return rows

    def one_seed(seed, with_control):
        params = model.init_params(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, seq), 0, cfg.vocab_size)
        batch = model.shard_batch(dict(
            tokens=tokens, positions=jnp.arange(seq)[None], labels=jnp.roll(tokens, -1, 1),
            loss_mask=jnp.ones((1, seq), jnp.float32).at[:, -1].set(0.0)))
        routers = [lp["router"] for lp in params["layers"] if "router" in lp]

        def picks_of(seen):
            """(routed blocks, S, k) as the program picks: the k largest of score + bias."""
            return jnp.stack([jax.lax.top_k(jax.nn.sigmoid(jnp.asarray(logits)) + router[ROUTER_BIAS], k)[1]
                              for (_, logits), router in zip(seen, routers)])

        def program():
            """The cell's own loss (scanned runs, recomputation) and gradients,
            and what each block's router was given and made of it IN THAT VERY
            PROGRAM: [(y, logits)] in the blocks' order (a block is known by
            its router kernel's first entry)."""
            handed = {}

            def keep(tag, y, logits):
                handed.setdefault(float(tag), (np.asarray(y.astype(jnp.float32)), np.asarray(logits)))

            def spy(y, kernel):
                logits = committed_router(y, kernel)
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
                     "expert_load_max_over_mean": float(parts["expert_load_max_over_mean"]),
                     "linear_decay_mean": float(parts["linear_decay_mean"]),
                     "linear_state_abs_max": float(parts["linear_state_abs_max"])}
            seen = [handed[float(router["kernel"][0, 0])] for router in routers]
            return parts, grads, seen

        def reference(forced=None):
            """(parts, gradients, picks); `forced` (routed blocks, S, k) holds it to a routing."""
            given = dict(batch) if forced is None else {**batch, "forced_experts": forced[None]}
            (_, parts), grads = reference_grad(params, given)
            picks = parts.pop("picks")[0]
            return {name: float(v) for name, v in parts.items()}, jax.device_get(grads), picks

        def as_sets(picks):
            return np.asarray(jnp.sum(jax.nn.one_hot(picks, cfg.num_experts), axis=-2))  # (blocks, S, E)

        def leaf_errors(got, want):
            want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
            rows = {}
            for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
                r, g = np.asarray(want[path], np.float64), np.asarray(g, np.float64)
                norm = np.linalg.norm(r)
                rows[jax.tree_util.keystr(path)] = float(np.linalg.norm(g - r) / norm) if norm else float(
                    np.linalg.norm(g))
            return rows

        def router_error(seen):
            """Worst block: rms of (logits - float64 product) over rms of the product."""
            worst = 0.0
            for (y, logits), router in zip(seen, routers):
                exact = np.asarray(y, np.float64) @ np.asarray(router["kernel"], np.float64)
                worst = max(worst, float(np.sqrt(np.mean((np.asarray(logits, np.float64) - exact) ** 2)
                                                 / np.mean(exact ** 2))))
            return worst

        out = {"seed": seed, "passes": mixer_errors(params, tokens, with_control),
               "core": core_errors(params, tokens, with_control)}
        ref_parts, ref_grads, ref_picks = reference()
        ref_sets = as_sets(ref_picks)
        out["reference"] = ref_parts
        first, held = cfg.held_experts
        parts, grads, seen = program()
        picks = picks_of(seen)
        differs = np.any(as_sets(picks) != ref_sets, axis=-1)  # (blocks, S)
        crosses = np.any((as_sets(picks) != ref_sets)[..., first:first + held], axis=-1)
        free = leaf_errors(grads, ref_grads)
        same = leaf_errors(grads, reference(forced=picks)[1])
        worst = lambda i: max(row["program"][i] for row in out["core"].values())  # noqa: E731
        measured = {
            "loss": abs(parts["loss"] - ref_parts["loss"]),
            "router": router_error(seen),  # on the very rows it was given
            "core_o": worst(0), "core_o_last_chunk": worst(1), "core_state": worst(2),
            "passes_out": out["passes"]["program"]["passes_out"],
            "passes_worst_leaf": out["passes"]["program"]["passes_worst_leaf"],
            "tokens_flipped_share": float(np.mean(np.any(differs, axis=0))),
            "worst_leaf_same_routing": max(same.values()),
            "worst_leaf": max(free.values()),
        }
        out["program"] = {
            **parts, "reference_loss": ref_parts["loss"],
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
            "core", json.dumps(out["core"]), "passes", json.dumps(out["passes"]), flush=True)
        if with_control:
            measured = dict(zip(("core_o", "core_o_last_chunk", "core_state"),
                                out["core"][kda_layers[0]]["control_bf16_state"]))
            outside = {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]}
            out["control_bf16_state"] = {"measured": measured, "outside_limits": outside}
            verdicts["control_bf16_state"] = not outside
            print("seed %d" % seed, "control_bf16_state", "PASS" if not outside else "FAIL",
                  json.dumps(out["control_bf16_state"]), flush=True)
            measured = {n: out["passes"]["control_dropped_tap"][n] for n in ("passes_out", "passes_worst_leaf")}
            outside = {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]}
            out["control_dropped_tap"] = {"measured": measured, "outside_limits": outside}
            verdicts["control_dropped_tap"] = not outside
            print("seed %d" % seed, "control_dropped_tap", "PASS" if not outside else "FAIL",
                  json.dumps(out["control_dropped_tap"]), flush=True)
        return out, verdicts

    runs, sound, controls_fail = [], True, {"control_bf16_state": False, "control_dropped_tap": False}
    for i, seed in enumerate(seeds):
        out, verdicts = one_seed(seed, with_control=i == 0)
        runs.append(out)
        sound = sound and verdicts["program"]
        controls_fail = {name: failed or not verdicts.get(name, True) for name, failed in controls_fail.items()}
    largest = {n: max(r["program"]["measured"][n] for r in runs) for n in LIMITS}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kimilin_chip_check.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "tokens": seq, "limits": LIMITS,
                   "largest_over_seeds": largest, "runs": runs}, f, indent=1)
    ok = sound and all(controls_fail.values())
    print("VERDICT %s: the program within its limits on seeds %s: %s; the controls outside: %s; "
          "largest reading [limit]: %s; the bf16-state control: %s; the dropped-tap control: %s" % (
              "PASS" if ok else "FAIL", seeds, sound, json.dumps(controls_fail),
              json.dumps({n: [largest[n], LIMITS[n]] for n in LIMITS}),
              json.dumps(runs[0]["control_bf16_state"]["measured"]),
              json.dumps(runs[0]["control_dropped_tap"]["measured"])), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

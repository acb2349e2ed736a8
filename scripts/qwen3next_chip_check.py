#!/usr/bin/env python3
"""Qwen3-Next at its published widths and the timed sizes on the chip, program
against plain reference, outside any timed window (the `model-configs` guide's
section 3, item 3):

    chiprun -- python3 scripts/qwen3next_chip_check.py [--seeds N,N,...]

One seeded 8192-token sequence a seed through the benchmark's own configuration
(benchmarks/configs/qwen3-next-80b-a3b-d4-e32-v8.json: three linear layers and
one attention layer, 32 of 512 experts held, 1/8 of the vocabulary) and the
cell's own layout (one chip, `--checkpoint 1`, scanned runs) against the
float32 reference on the same weights and batch. A seed reads:

- the loss and its parts;
- the router's own arithmetic against numpy's float64 on the rows it was
  given, a layer (handed out of the very program whose gradients are compared,
  by a `jax.debug.callback` around the router), and the share of tokens whose
  pick differs from the float32 reference's in any layer;
- **the delta rule's core**: layer 0's q, k, v, g, beta as the program makes
  them (bf16 operands, float32 gates), through `ops/linear_attention.
  gated_delta_rule` in the form the chip takes (the Pallas kernels; the XLA
  form's reading is kept beside it) and through the reference's
  token-by-token recurrence in float32 (`delta_rule`): the relative error of
  `o` over the whole sequence and over the LAST 64 tokens, where 8192 tokens
  of carried state have piled up, and of the final states themselves, these
  against the same recurrence in FLOAT64 ON THE HOST (`final_states_float64`:
  the float32 recurrence on the chip is itself 3e-4 off where a head forgets
  least, its `exp` reading low 8192 times in a row, which four seeds of PR 35
  did not show and two fresh ones of PR 36 did);
- **the passes around the core** (PR 38: `conv_norm_*`, `gated_norm_*`, the
  convolution, SiLU, the L2 norms and the gated RMSNorm as Pallas passes):
  layer 0's whole mixer through them against the XLA form of the same
  arithmetic (`mixer_form` held to "xla"; the core is the kernels' in both)
  on the same weights and the same normed activations: the relative error of
  the mixer's output and, of a probe's gradient, the worst leaf's (the
  mixer's seven leaves and its input);
- every leaf's gradient twice, against the reference as it routes itself and
  against the reference HELD TO THE PROGRAM'S ROUTING (`forced_experts`).

**A control that breaks the passes, on the first seed, which must FAIL**: the
same mixer through the kernels with the convolution's first tap dropped (a
three-tap convolution), against the XLA form with all four.

**Two controls in the next lower precision, on the first seed, each of which
must FAIL at least one limit**: the router's matmul in bf16 (the whole
program run again), and the core with its carried state rounded to bf16 after
every chunk (the XLA form, `impl="xla"`, its scan replaced by one that rounds
with `jax.lax.reduce_precision`: a cast there and back the TPU compiler takes
out; the kernels call neither `_carry` nor `_head_core`, and what the control
shows, that the measure tells a rounded state from a float32 one, it shows
on either form; compiled as a program of its own: traced into one jit with
the program's core the two read each other's precision, 1.8e-3 where the
program alone reads 9.4e-5). Writes
`chiprun_out/qwen3next_chip_check.json`; its LAST line of output is the verdict
with each measure's largest reading over the seeds beside its limit; exits 1
unless the program passes on every seed and both controls fail. Refuses to run
where jax finds no TPU.

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
CELL = "qwen3next-c1-s8k"
# measure -> most allowed, for the program as the cell runs it (bf16 compute).
# Two readings each (my chip runs, PR 35, call 8: seeds 32, 7, 2024, 11; the
# controls on seed 32, the bf16 state's on all four): the largest the program
# gave over the seeds, and the control's.
#   loss                     4.0e-4   bf16 router 2.3e-4
#   router                   9.8e-8   bf16 router 2.3e-3
#   core_state               3.6e-6   bf16 state 1.8e-3 to 2.6e-3   (PR 36, seeds 32, 271828, 31337, 7, against float64 on the host: the
#                                     kernels 1.8e-6 to 3.6e-6, the XLA form 1.8e-6 to 7.2e-6; against the float32 recurrence ON THE CHIP,
#                                     as PR 35 read it, both forms 2.9e-5 to 4.9e-4: that recurrence's own error, 6.5e-5 to 4.9e-4)
#   core_o                   2.85e-3  bf16 state 2.95e-3  (bf16 operands on the way to the output: 2^-9)
#   core_o_last_chunk        2.88e-3  bf16 state 2.97e-3
#   tokens_flipped_share     0.627    bf16 router 0.647   (any of 4 layers x 10 picks of 512; 0.10 across the held 32)
#   worst_leaf_same_routing  0.057    bf16 router 0.057   (the attention layer's router kernel; median leaf 0.036)
#   worst_leaf               0.206    bf16 router 0.200   (a router kernel: its gradient comes through the 32 held experts alone)
# `router` tells a bf16 router apart by four orders of magnitude and
# `core_state` a bf16 state by nearly three: each limit lies between its two
# readings, 100 x over the one and 1 / 230 of the other, 110 x over the one and
# 1 / 4.5 of the other's smallest. Neither control moves the loss, the core's
# output or any gradient further than the bf16 stream they read already
# does, so the other limits cannot lie between two readings: they stand at
# about 1.4 times the program's largest, the loss at the cell's own
# `reference_loss.abs`
#   passes_out               7.1e-3   dropped tap 0.79    (PR 38, call 4, seeds 32, 7: layer 0's mixer through the passes' kernels against
#   passes_worst_leaf        9.8e-3   dropped tap 0.90     the XLA form on the same weights, both in bf16; limits 4 x and 5 x the program's,
#                                                          1 / 26 and 1 / 18 of the control's)
LIMITS = {"loss": 2e-3, "router": 1e-5, "core_state": 4e-4, "core_o": 4e-3, "core_o_last_chunk": 4e-3,
          "tokens_flipped_share": 0.85, "worst_leaf_same_routing": 0.08, "worst_leaf": 0.29,
          "passes_out": 0.03, "passes_worst_leaf": 0.05}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="32,7,2024,11",
                        help="comma-separated; the controls run on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("qwen3next_chip_check needs a TPU; found %s" % jax.devices()[0].platform,
              file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models.parts.common import _norm
    from galvatron_tpu.models.parts.embed_head import embed_tokens
    from galvatron_tpu.models.parts import linear as part
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
    committed = moe.router_logits
    committed_core, committed_form = part.gated_delta_rule, part.mixer_form

    def xla_form(*_, **__):
        return "xla"

    def reference_loss(p, given):
        parts = ref.loss_parts(p, given, fields)
        return parts["loss"], parts

    reference_grad = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))  # traced twice: free, forced

    committed_carry = L._carry

    def carry_bf16(m, b):
        """`_carry` with the state rounded to bf16 after every chunk (by
        `reduce_precision`: a cast there and back the compiler takes out)."""
        def step(state, mb):
            new = L._mm(mb[0], state) + mb[1]
            return jax.lax.reduce_precision(new, exponent_bits=8, mantissa_bits=7), state

        last, starts = jax.lax.scan(step, jnp.zeros_like(b[0]), (m, b))
        return starts, last

    def core_with_bf16_state(*operands):
        # under a new function object: `jax.checkpoint` keeps a function's trace
        head_core = L._head_core
        L._carry, L._head_core = carry_bf16, lambda *a: head_core(*a)
        try:
            return L.gated_delta_rule(*operands, impl="xla")
        finally:
            L._carry, L._head_core = committed_carry, head_core

    @jax.jit
    def core_operands(params, tokens):
        """Layer 0's q, k, v, g, beta as the program makes them."""
        lcfg = cfg.layer_config(cfg.layer_kinds()[0])
        lp = params["layers"][0]
        x = embed_tokens(params["embed"], tokens, jnp.arange(seq)[None], cfg)
        box = {}

        def spy(*operands, **where):
            box["operands"] = operands
            return committed_core(*operands, **where)

        part.gated_delta_rule, part.mixer_form = spy, xla_form  # the form that hands the core its operands
        try:
            part.linear_mixer(lp, _norm(x, lp["ln1"], lcfg), None, lcfg)
        finally:
            part.gated_delta_rule, part.mixer_form = committed_core, committed_form
        return box["operands"]

    def mixer_errors(params, tokens, with_control):
        """Layer 0's mixer (both projections, the passes, the core) on the
        normed activations the program hands it, through the passes' kernels
        and through the XLA form, each a program of its own: the output, and
        every leaf's gradient of a fixed probe of it."""
        lcfg = cfg.layer_config(cfg.layer_kinds()[0])
        lp = params["layers"][0]
        y = jax.jit(lambda: _norm(embed_tokens(params["embed"], tokens, jnp.arange(seq)[None], cfg),
                                    lp["ln1"], lcfg))()
        probe = jax.random.normal(jax.random.PRNGKey(17), y.shape, jnp.float32)

        def run(form, taps_dropped=0):
            def of(linear, y):
                linear = dict(linear, conv=linear["conv"].at[:, :taps_dropped].set(0.0))
                part.mixer_form = form
                try:
                    out = part.linear_mixer({"linear": linear}, y, None, lcfg)[0]
                finally:
                    part.mixer_form = committed_form
                return jnp.sum(out.astype(jnp.float32) * probe), out

            fn = jax.jit(jax.value_and_grad(of, argnums=(0, 1), has_aux=True))
            if form is committed_form:
                text = fn.lower(lp["linear"], y).as_text()
                assert "conv_norm_fwd" in text and "gated_norm_bwd" in text, (
                    "on the chip the passes' form is the kernels'")
            (_, out), grads = fn(lp["linear"], y)
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
        serves = v.shape[2] // q.shape[2]
        with jax.default_matmul_precision("highest"):
            return ref.delta_rule(*(t[0].astype(jnp.float32) for t in (
                jnp.repeat(q, serves, axis=2), jnp.repeat(kk, serves, axis=2), v, g, beta)))

    def final_states_float64(q, kk, v, g, beta):
        """The recurrence's final states in float64 on the host (numpy). What
        the states are held to: the chip's float32 `exp` reads 1.4e-6 low on
        average, and 8192 factors `exp(g_t)` multiplied token by token carry
        that into the float32 recurrence's state (3e-4 in a head that forgets
        1e-4 a token, where either chunked form is within 5e-6: PERF.md, PR
        36), which is the reference's error and not the program's."""
        q, kk, v, g, beta = (np.asarray(t[0].astype(jnp.float32), np.float64) for t in (q, kk, v, g, beta))
        kk = np.repeat(kk, v.shape[1] // kk.shape[1], axis=1)
        state = np.zeros((v.shape[1], kk.shape[2], v.shape[2]))
        for t in range(v.shape[0]):
            state *= np.exp(g[t])[:, None, None]
            u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", state, kk[t]))
            state += kk[t][:, :, None] * u[:, None, :]
        return state

    def core_errors(params, tokens):
        """Layer 0's core on the operands the program makes for it: the form
        the chip takes (the kernels), the XLA form and the XLA form with a
        bf16 state, each a program of its own: the output against the
        recurrence token by token on the chip, the final states against the
        same recurrence in float64 on the host."""
        operands = core_operands(params, tokens)
        exact, state_on_chip = recurrence(*operands)
        exact_state = final_states_float64(*operands)
        rel = lambda d, e: float(np.linalg.norm(d) / np.linalg.norm(e))  # noqa: E731

        def error(core):
            o, state = jax.jit(core)(*operands)
            diff = o[0].astype(jnp.float32) - exact
            return (rel(diff, exact), rel(diff[-L.CHUNK:], exact[-L.CHUNK:]),
                    rel(np.asarray(state[0], np.float64) - exact_state, exact_state))

        assert "gdn_fwd" in jax.jit(committed_core).lower(*operands).as_text(), (
            "on the chip the core's form is the kernels'")
        return {"program": error(committed_core),
                "xla_form": error(lambda *a: L.gated_delta_rule(*a, impl="xla")),
                "control_bf16_state": error(core_with_bf16_state),
                "recurrence_float32_on_chip_state": rel(np.asarray(state_on_chip, np.float64) - exact_state,
                                                        exact_state),
                "decay_mean": float(jnp.mean(jnp.exp(operands[3]))),
                "o_rms": float(jnp.sqrt(jnp.mean(exact * exact)))}

    def one_seed(seed, with_control):
        params = model.init_params(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, seq), 0, cfg.vocab_size)
        batch = model.shard_batch(dict(
            tokens=tokens, positions=jnp.arange(seq)[None], labels=jnp.roll(tokens, -1, 1),
            loss_mask=jnp.ones((1, seq), jnp.float32).at[:, -1].set(0.0)))
        routers = [lp["router"]["kernel"] for lp in params["layers"]]

        def bf16_router(y, kernel):
            return (y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(jnp.float32)

        def picks_of(seen):
            """(layers, S, k) as the program picks: the softmax's k largest."""
            return jnp.stack([jax.lax.top_k(jax.nn.softmax(jnp.asarray(logits), axis=-1), k)[1]
                              for _, logits in seen])

        def program(logits_fn):
            """The cell's own loss (scanned runs, recomputation) and gradients,
            and what each layer's router was given and made of it IN THAT VERY
            PROGRAM: [(y, logits)] in the layers' order (a layer is known by
            its router kernel's first entry)."""
            handed = {}

            def keep(tag, y, logits):
                handed.setdefault(float(tag), (np.asarray(y.astype(jnp.float32)), np.asarray(logits)))

            def spy(y, kernel):
                logits = logits_fn(y, kernel)
                jax.debug.callback(keep, kernel[0, 0], y, logits)
                return logits

            moe.router_logits = spy
            try:
                (total, parts), grads = jax.jit(jax.value_and_grad(
                    model.loss_parts_fn, has_aux=True))(params, batch)
                grads = jax.device_get(grads)
                jax.effects_barrier()
            finally:
                moe.router_logits = committed
            parts = {"loss": float(total), "ce": float(parts["loss_ce"]),
                     "load_balance": float(parts["loss_load_balance"]),
                     "expert_rows_held_over_even": float(parts["expert_rows_held_over_even"]),
                     "expert_load_max_over_mean": float(parts["expert_load_max_over_mean"]),
                     "linear_decay_mean": float(parts["linear_decay_mean"]),
                     "linear_state_abs_max": float(parts["linear_state_abs_max"])}
            seen = [handed[float(kernel[0, 0])] for kernel in routers]
            return parts, grads, seen

        def reference(forced=None):
            """(parts, gradients, picks); `forced` (layers, S, k) holds it to a routing."""
            given = dict(batch) if forced is None else {**batch, "forced_experts": forced[None]}
            (_, parts), grads = reference_grad(params, given)
            picks = parts.pop("picks")[0]
            return {name: float(v) for name, v in parts.items()}, jax.device_get(grads), picks

        def as_sets(picks):
            return np.asarray(jnp.sum(jax.nn.one_hot(picks, cfg.num_experts), axis=-2))  # (layers, S, E)

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
            """Worst layer: rms of (logits - float64 product) over rms of the product."""
            worst = 0.0
            for (y, logits), kernel in zip(seen, routers):
                exact = np.asarray(y, np.float64) @ np.asarray(kernel, np.float64)
                worst = max(worst, float(np.sqrt(np.mean((np.asarray(logits, np.float64) - exact) ** 2)
                                                 / np.mean(exact ** 2))))
            return worst

        out = {"seed": seed}
        out["core"] = core_errors(params, tokens)
        out["passes"] = mixer_errors(params, tokens, with_control)
        ref_parts, ref_grads, ref_picks = reference()
        ref_sets = as_sets(ref_picks)
        out["reference"] = ref_parts
        first, held = cfg.held_experts

        def flips(picks):
            differs = np.any(as_sets(picks) != ref_sets, axis=-1)  # (layers, S)
            crosses = np.any((as_sets(picks) != ref_sets)[..., first:first + held], axis=-1)
            return {"tokens_flipped_share": float(np.mean(np.any(differs, axis=0))),
                    "picks_flipped_share_a_layer": [float(v) for v in np.mean(differs, axis=1)],
                    "tokens_flipped_across_the_held_set_share": float(np.mean(np.any(crosses, axis=0)))}

        verdicts = {}
        passes = (("program", committed),) + ((("control_bf16_router", bf16_router),) if with_control else ())
        for name, logits_fn in passes:
            parts, grads, seen = program(logits_fn)
            picks = picks_of(seen)
            free = leaf_errors(grads, ref_grads)
            same = leaf_errors(grads, reference(forced=picks)[1])
            flipped = flips(picks)
            measured = {
                "loss": abs(parts["loss"] - ref_parts["loss"]),
                "router": router_error(seen),  # on the very rows it was given
                "core_o": out["core"]["program"][0],
                "core_o_last_chunk": out["core"]["program"][1],
                "core_state": out["core"]["program"][2],
                "passes_out": out["passes"]["program"]["passes_out"],
                "passes_worst_leaf": out["passes"]["program"]["passes_worst_leaf"],
                "tokens_flipped_share": flipped["tokens_flipped_share"],
                "worst_leaf_same_routing": max(same.values()),
                "worst_leaf": max(free.values()),
            }
            out[name] = {
                **parts, "abs_err": {n: abs(parts[n] - ref_parts[n]) for n in ("loss", "ce", "load_balance")},
                **flipped, "measured": measured,
                "outside_limits": {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]},
                "worst_leaf_name": max(free, key=free.get),
                "worst_leaf_same_routing_name": max(same, key=same.get),
                "leaves_against_the_reference_as_it_routes": free,
                "leaves_against_the_reference_held_to_this_routing": same,
            }
            verdicts[name] = not out[name]["outside_limits"]
            print("seed %d" % seed, name, "PASS" if verdicts[name] else "FAIL", json.dumps(
                {n: v for n, v in out[name].items() if not n.startswith("leaves")}), flush=True)
            del seen, grads
        if with_control:
            measured = dict(zip(("core_o", "core_o_last_chunk", "core_state"),
                                out["core"]["control_bf16_state"]))
            outside = {n: [v, LIMITS[n]] for n, v in measured.items() if v > LIMITS[n]}
            out["control_bf16_state"] = {"measured": measured, "outside_limits": outside,
                                         "xla_form_float32_state": out["core"]["xla_form"]}
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

    runs, sound, controls_fail = [], True, {"control_bf16_router": False, "control_bf16_state": False,
                                            "control_dropped_tap": False}
    for i, seed in enumerate(seeds):
        out, verdicts = one_seed(seed, with_control=i == 0)
        runs.append(out)
        sound = sound and verdicts["program"]
        for name in controls_fail:
            controls_fail[name] = controls_fail[name] or not verdicts.get(name, True)
    largest = {n: max(r["program"]["measured"][n] for r in runs) for n in LIMITS}
    xla_core = [max(r["core"]["xla_form"][i] for r in runs) for i in range(3)]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "qwen3next_chip_check.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "tokens": seq, "limits": LIMITS,
                   "largest_over_seeds": largest, "runs": runs,
                   "xla_form_core_o_last_state_largest": xla_core}, f, indent=1)
    ok = sound and all(controls_fail.values())
    print("VERDICT %s: the program within its limits on seeds %s: %s; the controls outside: %s; "
          "largest reading [limit]: %s; the XLA form's core (o, last 64, state): %s; "
          "the bf16-router control: %s; the bf16-state control: %s; the dropped-tap control: %s" % (
              "PASS" if ok else "FAIL", seeds, sound, json.dumps(controls_fail),
              json.dumps({n: [largest[n], LIMITS[n]] for n in LIMITS}), json.dumps(xla_core),
              json.dumps(runs[0]["control_bf16_router"]["measured"]),
              json.dumps(runs[0]["control_bf16_state"]["measured"]),
              json.dumps(runs[0]["control_dropped_tap"]["measured"])), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

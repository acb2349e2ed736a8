#!/usr/bin/env python3
"""GLM-4.7-Flash at its published widths and the timed sizes on the chip,
program against plain reference, outside any timed window (the
`model-configs` guide's section 3, item 3):

    chiprun -- python3 scripts/glm47f_chip_check.py [--seeds N,N,...]

One seeded 8192-token sequence a seed through the benchmark's own configuration
(benchmarks/configs/glm-4.7-flash-d5-e8-v8.json: 1 + 4 layers, 8 of 64 experts
held, 1/8 of the vocabulary, the MTP module) and the cell's own layout (one
chip, `--checkpoint 1`, scanned runs) against the float32 reference on the
same weights and batch, in two passes: the program as the cell runs it (bf16
compute, float32 router) and the control in the next lower precision (the
router's matmul in bf16), which must FAIL. Each pass reads the loss and its
two parts; the router's own arithmetic against numpy's float64 on the rows it
was given, and the experts it picked, a routed block (handed out of the
very program whose gradients are compared, by a `jax.debug.callback` around
the router); the share of tokens whose pick differs from the float32
reference's, in any of the five routed blocks; and every leaf's gradient
twice, against the reference as it routes itself and against the reference
HELD TO THE PROGRAM'S ROUTING (`batch["forced_experts"]`). (The program has
no float32 pass at this size: the flash kernel's float32 blocks at head_dim
256 exceed the scoped VMEM, and XLA's attention would hold 20 x 8192 x 8192
scores.) The control runs on the first seed alone. Writes
`chiprun_out/glm47f_chip_check.json`; its LAST line of output is the verdict
with each measure's largest reading over the seeds beside its limit; exits 1
unless the program passes on every seed and the control fails. Refuses to run
where jax finds no TPU.

Why two gradient comparisons: scripts/olmoe_chip_check.py's docstring. Here
a flip reaches this chip's output only when it crosses the held set (8 of 64
experts), so the free-running comparison is nearer the held one than OLMoE's.

The limits (LIMITS) and the two readings behind each are beside them below.
The control is held where a bf16 router shows, its logits against float64 on
the very rows it was given; the loss and the gradients do not tell it apart.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "glm47f-c1-s8k"
# measure -> most allowed, for the program as the cell runs it (bf16 compute).
# Two readings each (my chip runs, PR 32, second session: seeds 32, 7, 2024, 11;
# the control on seed 32): the largest the program gave over the seeds, and the
# bf16-router control's.
#   loss                     4.1e-4   control 5.4e-4
#   router                   9.9e-8   control 1.7e-3
#   tokens_flipped_share     0.172    control 0.169
#   worst_leaf_same_routing  0.080    control 0.056  (a router kernel; held experts' kernels 0.050, median leaf 0.016)
#   worst_leaf               0.232    control 0.220  (a router kernel: its gradient comes through the 8 held experts alone)
# ONLY `router` tells the two apart, by four orders of magnitude, and its
# limit lies between the readings (100 x the one, 1/170 of the other). A bf16
# router moves the loss and the gradients no further than the bf16 residual
# stream it reads already has (its picks differ from the float32 reference's
# for 16.9 % of the tokens against 16.8 %), so the other limits cannot lie
# between two readings: they stand at about 1.4 times the program's largest,
# the loss at the cell's own `reference_loss.abs` (three times the widest gap
# of the cell's 24 runs, 7.1e-4)
LIMITS = {"loss": 2e-3, "router": 1e-5, "tokens_flipped_share": 0.24,
          "worst_leaf_same_routing": 0.115, "worst_leaf": 0.33}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="32,7,2024", help="comma-separated; the control runs on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("glm47f_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
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

    def reference_loss(p, given):
        parts = ref.loss_parts(p, given, fields)
        return parts["loss"], parts

    reference_grad = jax.jit(jax.value_and_grad(reference_loss, has_aux=True))  # traced twice: free, forced

    def one_seed(seed, with_control):
        params = model.init_params(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, seq), 0, cfg.vocab_size)
        batch = model.shard_batch(dict(
            tokens=tokens, positions=jnp.arange(seq)[None], labels=jnp.roll(tokens, -1, 1),
            loss_mask=jnp.ones((1, seq), jnp.float32).at[:, -1].set(0.0)))
        routers = [r["kernel"] for r in M.router_bias_leaves(params)]
        biases = [r[ROUTER_BIAS] for r in M.router_bias_leaves(params)]

        def bf16_router(y, kernel):
            return (y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(jnp.float32)

        def picks_of(seen):
            """(blocks, S, k) as the program picks: sigmoid scores plus the bias."""
            return jnp.stack([jax.lax.top_k(jax.nn.sigmoid(jnp.asarray(logits)) + b, k)[1]
                              for (_, logits), b in zip(seen, biases)])

        def program(logits_fn):
            """The cell's own loss (scanned runs, recomputation) and gradients,
            and what each routed block's router was given and made of it IN THAT
            VERY PROGRAM: [(y, logits)] in the blocks' order, handed out by a
            `jax.debug.callback` (the recomputation hands out the same values a
            second time; a block is known by its router kernel's first entry)."""
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
            parts = {"loss": float(total), "ce": float(parts["loss_ce"]), "mtp": float(parts["loss_mtp"]),
                     "expert_rows_held_over_even": float(parts["expert_rows_held_over_even"]),
                     "expert_load_max_over_mean": float(parts["expert_load_max_over_mean"])}
            seen = [handed[float(kernel[0, 0])] for kernel in routers]
            return parts, grads, seen

        def reference(forced=None):
            """(parts, gradients); `forced` (blocks, S, k) holds it to a routing."""
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
            for (y, logits), kernel in zip(seen, routers):
                exact = np.asarray(y, np.float64) @ np.asarray(kernel, np.float64)
                worst = max(worst, float(np.sqrt(np.mean((np.asarray(logits, np.float64) - exact) ** 2)
                                                 / np.mean(exact ** 2))))
            return worst

        out = {"seed": seed}
        ref_parts, ref_grads, ref_picks = reference()
        ref_sets = as_sets(ref_picks)
        out["reference"] = ref_parts
        first, held = cfg.held_experts

        def flips(picks):
            differs = np.any(as_sets(picks) != ref_sets, axis=-1)  # (blocks, S)
            crosses = np.any((as_sets(picks) != ref_sets)[..., first:first + held], axis=-1)
            return {"tokens_flipped_share": float(np.mean(np.any(differs, axis=0))),
                    "picks_flipped_share_a_block": [float(v) for v in np.mean(differs, axis=1)],
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
                "tokens_flipped_share": flipped["tokens_flipped_share"],
                "worst_leaf_same_routing": max(same.values()),
                "worst_leaf": max(free.values()),
            }
            out[name] = {
                **parts, "abs_err": {n: abs(parts[n] - ref_parts[n]) for n in ref_parts}, **flipped,
                "measured": measured,
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
        return out, verdicts

    runs, sound, control_fails = [], True, False
    for i, seed in enumerate(seeds):
        out, verdicts = one_seed(seed, with_control=i == 0)
        runs.append(out)
        sound = sound and verdicts["program"]
        control_fails = control_fails or not verdicts.get("control_bf16_router", True)
    largest = {n: max(r["program"]["measured"][n] for r in runs) for n in LIMITS}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "glm47f_chip_check.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "tokens": seq, "limits": LIMITS,
                   "largest_over_seeds": largest, "runs": runs}, f, indent=1)
    ok = sound and control_fails
    print("VERDICT %s: the program within its limits on seeds %s: %s; the bf16-router control outside: %s; "
          "largest reading [limit]: %s; the control: %s" % (
              "PASS" if ok else "FAIL", seeds, sound, control_fails,
              json.dumps({n: [largest[n], LIMITS[n]] for n in LIMITS}),
              json.dumps(runs[0]["control_bf16_router"]["measured"])), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

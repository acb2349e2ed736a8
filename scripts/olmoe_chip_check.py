#!/usr/bin/env python3
"""OLMoE at its published widths on the chip, program against plain reference,
outside any timed window (the `model-configs` guide's section 3, item 3):

    chiprun -- python3 scripts/olmoe_chip_check.py [--seed N]

One seeded 4096-token sequence through the benchmark's own configuration
(benchmarks/configs/olmoe-1b-7b-d1.json: one layer, 64 experts, the whole
vocabulary) against the float32 reference on the same weights, in three
passes: the program in float32 compute, the program as the cell runs it
(bf16 compute), and the control in the next lower precision (the router's
matmul in bf16), which must FAIL. Each pass reads, from inside the jitted
program (the router is wrapped, nothing else): the loss and its three parts;
the router's own arithmetic against numpy's float64 on the rows it was
given; how many tokens chose another set of experts than the reference's;
and every leaf's gradient twice, against the reference as it routes itself
and against the reference HELD TO THE PROGRAM'S ROUTING
(`batch["forced_experts"]`). Writes `chiprun_out/olmoe_chip_check.json`; exits
1 unless the two programs pass and the control fails. Refuses to run where
jax finds no TPU.

Why two comparisons. Top-k is discontinuous and, on untrained weights, an
expert's output is as large as the stream it is added to, so a token that
flips one expert moves its row of every gradient by much. Two evaluations of
the stream that differ in the last digits (bf16 against float32; on a TPU
even two float32 programs, which agree to 3e-4) flip a few nearly tied
tokens, and the free-running comparison then measures the flips, not the
arithmetic. Held to one routing it measures the arithmetic.

The limits (LIMITS), each from the measurement in PERF.md section 6 with room
for other seeds. The control is held where a bf16 router shows: its logits
against float64 on the very rows it was given (1e-7 in float32, 2e-3 in
bf16; limit 1e-5). Against the stream's own bf16 rounding it adds little.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "olmoe-c1-s4k"
# pass -> {measure: most allowed}
LIMITS = {
    "float32": {"loss": 3e-4, "router": 1e-5, "tokens_flipped_share": 0.01,
                "worst_leaf_same_routing": 2e-3, "worst_leaf": 0.03},
    "bf16": {"loss": 2e-3, "router": 1e-5, "tokens_flipped_share": 0.08,
             "worst_leaf_same_routing": 0.03, "worst_leaf": 0.10},
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=27)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("olmoe_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu.models import base as M
    from galvatron_tpu.ops import moe

    cell = cells.load_cell(ROOT, CELL)
    ref = cells.load_module(ROOT, "benchmarks/references/%s.py" % cell.config["reference"])
    build = cells.import_attr(cell.config["program"]["config_fn"])
    seq = cell.traffic["seq_length"]
    cfg = build(cell.config["program"]["preset"],
                **{**cell.fields, "max_seq_len": seq, "compute_dtype": jnp.bfloat16})
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    params = jax.jit(lambda k: M.init_model_params(k, cfg))(jax.random.PRNGKey(args.seed))
    tokens = jax.random.randint(jax.random.PRNGKey(args.seed + 1), (1, seq), 0, cfg.vocab_size)
    batch = dict(tokens=tokens, positions=jnp.arange(seq)[None], labels=jnp.roll(tokens, -1, 1),
                 loss_mask=jnp.ones((1, seq), jnp.float32))
    router = params["layers"][0]["router"]["kernel"]
    committed = moe.router_logits

    def program(cfg, logits_fn):
        """The jitted program's loss parts and gradients, and what its router
        was given and made of it (read from inside the trace)."""
        seen = {}

        def spy(y, kernel):
            seen["y"], seen["logits"] = y, logits_fn(y, kernel)
            return seen["logits"]

        def loss(p):
            total, parts = M.lm_loss_fn(p, batch, cfg, with_parts=True)
            return total, (parts, seen["y"], seen["logits"])

        moe.router_logits = spy
        try:
            # float32 compute means float32 on the MXU too, not bf16 passes
            with jax.default_matmul_precision(
                    "highest" if cfg.compute_dtype == jnp.float32 else "default"):
                (total, (parts, y, logits)), grads = jax.jit(
                    jax.value_and_grad(loss, has_aux=True))(params)
        finally:
            moe.router_logits = committed
        parts = {"loss": float(total), "ce": float(parts["loss_ce"]),
                 "load_balance": float(parts["loss_load_balance"]),
                 "router_z": float(parts["loss_router_z"]),
                 "expert_load_max_over_mean": float(parts["expert_load_max_over_mean"])}
        return parts, jax.device_get(grads), y, logits

    def reference(forced=None):
        """(parts, gradients); `forced` (S, k) holds it to a routing."""
        given = batch if forced is None else {**batch, "forced_experts": forced[None, None]}

        def loss(p):
            parts = ref.loss_parts(p, given, fields)
            return parts["loss"], parts

        (_, parts), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return {k: float(v) for k, v in parts.items()}, jax.device_get(grads)

    @jax.jit
    def reference_choice(p):
        with jax.default_matmul_precision("highest"):
            lp = jax.tree.map(lambda a: a.astype(jnp.float32), p["layers"][0])
            eps = fields["layernorm_eps"]
            x = p["embed"]["wte"][tokens[0]]
            x = x + ref._attention(lp, ref._rms(x, lp["ln1"]["scale"], eps), batch["positions"][0], fields)
            logits = ref._rms(x, lp["ln2"]["scale"], eps) @ lp["router"]["kernel"]
            return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.experts_per_token)[1]

    def as_sets(chosen):
        return np.asarray(jnp.sum(jax.nn.one_hot(chosen, cfg.num_experts), axis=1))

    def leaf_errors(got, want):
        want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
        rows = {}
        for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
            r, g = np.asarray(want[path], np.float64), np.asarray(g, np.float64)
            rows[jax.tree_util.keystr(path)] = float(np.linalg.norm(g - r) / np.linalg.norm(r))
        return rows

    def bf16_router(y, kernel):
        return (y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(jnp.float32)

    out = {"device": jax.devices()[0].device_kind, "seed": args.seed, "tokens": seq}
    ref_parts, ref_grads = reference()
    ref_sets = as_sets(reference_choice(params))
    out["reference"] = ref_parts
    verdicts = {}
    cfg32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    for name, run_cfg, logits_fn, limits in (
            ("program_float32", cfg32, committed, LIMITS["float32"]),
            ("program", cfg, committed, LIMITS["bf16"]),
            ("control_bf16_router", cfg, bf16_router, LIMITS["bf16"])):
        parts, grads, y, logits = program(run_cfg, logits_fn)
        chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.experts_per_token)[1]
        flipped = int(np.sum(np.any(as_sets(chosen) != ref_sets, axis=1)))
        exact = np.asarray(y.astype(jnp.float32), np.float64) @ np.asarray(router, np.float64)
        free = leaf_errors(grads, ref_grads)
        same = leaf_errors(grads, reference(forced=chosen)[1])
        measured = {
            "loss": abs(parts["loss"] - ref_parts["loss"]),
            # the router's own arithmetic, on the very rows it was given
            "router": float(np.sqrt(np.mean((np.asarray(logits, np.float64) - exact) ** 2)
                                    / np.mean(exact ** 2))),
            "tokens_flipped_share": flipped / seq,
            "worst_leaf_same_routing": max(same.values()),
            "worst_leaf": max(free.values()),
        }
        out[name] = {
            **parts, "abs_err": {k: abs(parts[k] - ref_parts[k]) for k in ref_parts},
            "tokens_with_another_expert_set": flipped, "measured": measured,
            "outside_limits": {k: [v, limits[k]] for k, v in measured.items() if v > limits[k]},
            "leaves_against_the_reference_as_it_routes": free,
            "leaves_against_the_reference_held_to_this_routing": same,
        }
        verdicts[name] = not out[name]["outside_limits"]
        print(name, "PASS" if verdicts[name] else "FAIL", json.dumps(
            {k: v for k, v in out[name].items() if not k.startswith("leaves")}), flush=True)
        for leaf in free:
            print("   %-36s as it routes %.3e   same routing %.3e" % (leaf, free[leaf], same[leaf]),
                  flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "olmoe_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    ok = verdicts["program_float32"] and verdicts["program"] and not verdicts["control_bf16_router"]
    print("programs within their limits and the control outside: %s" % ok, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Phi-4-mini-flash at its published widths and the timed sizes on the chip,
program against plain reference, outside any timed window (the `model-configs`
guide's section 3, item 3):

    chiprun -- python3 scripts/phi4flash_chip_check.py [--seeds N,N,...]

One seeded 8192-token sequence a seed through the benchmark's own configuration
(benchmarks/configs/phi-4-mini-flash-d6-v8.json: published layers 0, 1, 16, 17,
18, 19, 1/8 of the tied vocabulary) and the cell's own layout (one chip,
`--checkpoint 1`, six runs of one layer) against the float32 reference on the
same weights and batch (its `jax.grad` computed in blocks: a layer, a block of
64 tokens of the recurrence and a block of 1024 queries recomputed at a time).
A seed reads:

- the loss;
- **every layer kind's output**: each of the six layers through the program's
  `layer_forward` on the REFERENCE's input to that layer (and, for a reader,
  the reference's memory or keys and values), against the reference's output
  of that layer; and what the two publishers hand on, against the reference's;
- every leaf's gradient, relative by the Frobenius norm: the worst leaf of
  all, and BY NAME the leaves that a READER's cotangent reaches: layer 16's
  `wx`, `wdt` and `A_log` (through layer 18's gated memory unit) and layer 17's
  `wkv` kernel and bias (through layer 19's queries);
- **the scan's core**: layer 16's x, dt, A, B, C, D as the program makes them
  (bf16 operands, float32 dt), through `ops/selective_scan.selective_scan` as
  the step runs it (since PR 58 the kernels `selscan_fwd` / `selscan_bwd` here,
  where jax finds a TPU): the relative error of m over the whole sequence and over
  the LAST chunk's tokens, where 8192 tokens of carried state have piled up,
  against the reference's token-by-token recurrence in float32 on the chip,
  and of the final states against the same recurrence in FLOAT64 ON THE HOST.

**Two controls on the first seed, each of which must FAIL at least one limit**:
the same core with its carried state rounded to bfloat16 after every token
(`state_dtype`, by `jax.lax.reduce_precision`), the next lower precision (the
XLA form's: the kernels hold a float32 state and nothing else); and
the program's gradients against a reference whose readers see the memory and K,
V behind a `stop_gradient` (`switch_off` "reader_cotangents"): what a step that
dropped a reader's cotangent would compute. Writes
`chiprun_out/phi4flash_chip_check.json`; its LAST line of output is the verdict
with each measure's largest reading over the seeds beside its limit; exits 1
unless the program passes on every seed and both controls fail. Refuses to run
where jax finds no TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "phi4flash-c1-s8k"
MAMBA_PUBLISHER = 2  # published layer 16, in the layers run
READER_REACHED = {"mamba.wx": "['layers'][2]['mamba']['wx']['kernel']",
                  "mamba.wdt": "['layers'][2]['mamba']['wdt']['kernel']",
                  "mamba.A_log": "['layers'][2]['mamba']['A_log']",
                  "full.wkv": "['layers'][3]['wkv']['kernel']",
                  "full.wkv_bias": "['layers'][3]['wkv']['bias']"}
# measure -> most allowed, for the program as the cell runs it (bf16 compute). Each with its two readings
# (my chip runs, PR 57, calls 3 to 5: seeds 57, 7; the controls on seed 57; call 5 is the committed scan,
# chunks of 128): the largest the program gave over the seeds and calls, and what a control gave.
#   loss               2.9e-4   (the cell's own `reference_loss.abs`; no control moves it: a bf16 state moves
#                               the scan's output by less than its bf16 rounding)
#   core_state         2.4e-6   bf16 state 1.74e-3 to 1.85e-3 (final states against float64 on the host; 1.7e-6
#                               to 1.8e-6 at chunks of 128; the float32 recurrence token by token ON THE CHIP
#                               reads 1.4e-4 to 1.5e-4 there: its own error, 8192 rounded multiply-adds in a
#                               row). The limit lies between the two readings, 29 x over the one, 1 / 25 of the other
#   core_m             1.669e-3 bf16 state 1.713e-3 to 1.728e-3 (m is rounded to bf16 on its way out: 2^-9)
#   core_m_last_chunk  1.684e-3 bf16 state 1.693e-3 to 1.706e-3
#   layer_output       9.5e-3   (the worst of the six layers' outputs and the three published tensors on the
#                               reference's inputs: layer 0, whose input is the embedding's rows rounded to
#                               bf16 before a LayerNorm; the other layers and the memory, k and v read 3.3e-3
#                               to 5.8e-3: what a bf16 stream does to one layer)
#   worst_leaf         0.0707   dropped reader cotangents 0.760 (calls 3 and 4: a lambda vector of layer 17 /
#                               layer 19, 0.053 and 0.071: sums over every token and pair that nearly cancel at
#                               a model's start, so their reading follows the rounding; call 5: layer 17's `wq`,
#                               0.0410 and 0.0418; the median leaf 0.032)
#   reader_reached     0.0418   dropped reader cotangents 0.760 (wx 0.0418, wdt 0.0417, A_log 0.0349, wkv
#                               0.0388, its bias 0.0279: the leaves by name, the largest of the calls; with the
#                               readers' cotangents dropped wx 0.68, wdt 0.58, A_log 0.56, wkv 0.76, its bias 0.45)
# `core_state` tells a bf16 state from a float32 one by three orders of magnitude, and the two gradient
# measures a dropped cotangent from a kept one by one: each limit lies between its two readings (the
# gradients' about twice the program's largest, five to nine times under the control's). The control in
# the next lower precision moves neither the core's output nor the layers' outputs further than the bf16
# stream they read already does, so those limits stand at about 1.5 times the program's largest, the loss
# at the cell's own `reference_loss.abs`.
LIMITS = {"loss": 2e-3, "core_state": 7e-5, "core_m": 2.6e-3, "core_m_last_chunk": 2.6e-3, "layer_output": 0.014,
          "worst_leaf": 0.15, "reader_reached": 0.08}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", default="57,7", help="comma-separated; the controls run on the first")
    args = parser.parse_args(argv)
    seeds = [int(n) for n in args.seeds.split(",")]

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("phi4flash_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import mamba as part
    from galvatron_tpu.models.parts.common import _norm
    from galvatron_tpu.ops import selective_scan as op
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
    kinds, shares, plan = cfg.layer_kinds(), cfg.shared(), ref.layer_plan(fields)
    committed_scan = part.selective_scan
    positions = jnp.arange(seq)[None]

    def rel(d, e):
        return float(np.linalg.norm(np.asarray(d, np.float64)) / np.linalg.norm(np.asarray(e, np.float64)))

    # ------------------------------------------------ every layer kind's output
    def reference_layers(params, tokens):
        """Each layer's input, output and what stands published after it, float32."""
        with jax.default_matmul_precision("highest"):
            p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            x, memory, kv, rows = p32["embed"]["wte"][tokens[0]], None, None, []
            for i, (lp, (kind, index)) in enumerate(zip(p32["layers"], plan)):
                out, memory, kv = ref.block(lp, x, memory, kv, kind, index, i, fields, frozenset())
                rows.append((x, out, memory, kv))
                x = out
        return rows

    def layer_errors(params, tokens):
        rows = jax.jit(reference_layers)(params, tokens)
        errors = {}
        for i, (x, want, memory, kv) in enumerate(rows):
            lcfg = cfg.layer_config(kinds[i])
            publish, reads = shares[i]
            before = rows[i - 1] if i else None
            handed = {}
            if "memory" in reads:
                handed["memory"] = before[2][None].astype(jnp.bfloat16)
            if "k" in reads:
                handed.update(k=before[3][0][None].astype(jnp.bfloat16), v=before[3][1][None].astype(jnp.bfloat16))
            out = jax.jit(lambda lp, x, handed, lcfg=lcfg, publish=publish: M.layer_forward(
                lp, x, positions, lcfg, publish=publish, **({"shared": handed} if handed else {})))(
                    params["layers"][i], x[None].astype(jnp.bfloat16), handed)
            out = out if isinstance(out, tuple) else (out,)
            name = "%d:%s" % (plan[i][1], kinds[i])
            errors[name] = rel(out[0][0].astype(jnp.float32) - want, want)
            if publish:
                for key, theirs in (("memory", memory),) if "memory" in publish else (("k", kv[0]), ("v", kv[1])):
                    errors[name + "->" + key] = rel(out[-1][key][0].astype(jnp.float32) - theirs, theirs)
        return errors

    # ------------------------------------------------------------ the scan's core
    @jax.jit
    def core_operands(params, x):
        """Layer 16's x, dt, A, B, C, D as the program makes them, on the reference's input to it."""
        lcfg = cfg.layer_config(kinds[MAMBA_PUBLISHER])
        lp = params["layers"][MAMBA_PUBLISHER]
        box = {}

        def spy(*operands, **kw):
            box["operands"] = operands
            return committed_scan(*operands, **kw)

        part.selective_scan = spy
        try:
            part.mamba_mixer(lp, _norm(x[None].astype(jnp.bfloat16), lp["ln1"], lcfg), None, lcfg)
        finally:
            part.selective_scan = committed_scan
        return box["operands"]

    @jax.jit
    def recurrence(x, dt, a, bm, cm, d):
        with jax.default_matmul_precision("highest"):
            return ref.selective_scan(*(t.astype(jnp.float32) for t in (x[0], dt[0], a, bm[0], cm[0], d)))

    def final_state_float64(x, dt, a, bm, cm, d):
        x, dt, a, bm = (np.asarray(t.astype(jnp.float32), np.float64) for t in (x[0], dt[0], a, bm[0]))
        state = np.zeros(a.shape)
        for t in range(x.shape[0]):
            state *= np.exp(dt[t][:, None] * a)
            state += (dt[t] * x[t])[:, None] * bm[t]
        return state

    def core_errors(params, layer_input, with_control):
        operands = core_operands(params, layer_input)
        exact, state_on_chip = recurrence(*operands)
        exact_state = final_state_float64(*operands)

        def error(**kw):
            m, state, peak = jax.jit(lambda *o: op.selective_scan(*o, **kw))(*operands)
            diff = m[0].astype(jnp.float32) - exact
            return {"core_m": rel(diff, exact), "core_m_last_chunk": rel(diff[-op.CHUNK:], exact[-op.CHUNK:]),
                    "core_state": rel(np.asarray(state[0], np.float64) - exact_state, exact_state),
                    "state_abs_max": float(peak)}

        out = {"program": error(),
               "recurrence_float32_on_chip_state": rel(np.asarray(state_on_chip, np.float64) - exact_state,
                                                       exact_state),
               "decay_mean": float(jnp.mean(jnp.exp(operands[1][..., None] * operands[2]))),
               "m_rms": float(jnp.sqrt(jnp.mean(exact * exact)))}
        if with_control:
            out["control_bf16_state"] = error(state_dtype=jnp.bfloat16)
        return out

    # ------------------------------------------------------------------ a seed
    def reference_grad(switch_off=()):
        return jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, fields, switch_off=switch_off)))

    def leaf_errors(grads, ref_grads):
        return {jax.tree_util.keystr(path): rel(np.asarray(g, np.float64) - np.asarray(r, np.float64), r)
                for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                        jax.tree_util.tree_leaves(ref_grads))
                if "lambda_init" not in jax.tree_util.keystr(path)}  # a constant: no gradient on either side

    def one_seed(seed, with_control):
        params = model.init_params(jax.random.PRNGKey(seed))
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, seq), 0, cfg.vocab_size)
        batch = model.shard_batch(dict(
            tokens=tokens, positions=positions, labels=jnp.roll(tokens, -1, 1),
            loss_mask=jnp.ones((1, seq), jnp.float32).at[:, -1].set(0.0)))
        step = jax.jit(jax.value_and_grad(model.loss_parts_fn, has_aux=True))
        text = step.lower(params, batch).as_text()
        (loss, parts), grads = step(params, batch)
        grads = jax.device_get(grads)
        ref_loss, ref_grads = reference_grad()(params, batch)
        leaves = leaf_errors(grads, jax.device_get(ref_grads))
        del ref_grads
        reached = {name: leaves[path] for name, path in READER_REACHED.items()}
        layers = layer_errors(params, tokens)
        layer_input = jax.jit(reference_layers)(params, tokens)[MAMBA_PUBLISHER][0]
        row = {"seed": seed, "loss": float(loss), "reference_loss": float(ref_loss),
               "selscan_state_abs_max": float(parts["selscan_state_abs_max"]),
               "published_mib": float(parts["published_mib"]),
               "kernels_in_step": text.count("tpu_custom_call"),
               "worst_leaf_name": max(leaves, key=leaves.get), "reader_reached_leaves": reached,
               "median_leaf": float(np.median(list(leaves.values()))), "layer_outputs": layers,
               "core": core_errors(params, layer_input, with_control)}
        row["measures"] = {"loss": abs(row["loss"] - row["reference_loss"]),
                           "worst_leaf": max(leaves.values()), "reader_reached": max(reached.values()),
                           "layer_output": max(layers.values()),
                           **{k: row["core"]["program"][k] for k in ("core_state", "core_m", "core_m_last_chunk")}}
        row["passes"] = all(v <= LIMITS[k] for k, v in row["measures"].items())
        if with_control:
            control = {k: row["core"]["control_bf16_state"][k] for k in ("core_state", "core_m", "core_m_last_chunk")}
            row["control_bf16_state_fails"] = [k for k, v in control.items() if v > LIMITS[k]]
            _, dropped = reference_grad(("reader_cotangents",))(params, batch)
            off = leaf_errors(grads, jax.device_get(dropped))
            del dropped
            row["dropped_reader_cotangents"] = {"worst_leaf": max(off.values()),
                                                **{name: off[path] for name, path in READER_REACHED.items()}}
            row["control_dropped_cotangents_fails"] = [
                k for k, v in (("worst_leaf", max(off.values())),
                               ("reader_reached", max(off[p] for p in READER_REACHED.values()))) if v > LIMITS[k]]
        print(json.dumps(row), flush=True)
        return row

    rows = [one_seed(seed, i == 0) for i, seed in enumerate(seeds)]
    largest = {k: max(r["measures"][k] for r in rows) for k in LIMITS}
    controls = {"bf16_state": rows[0]["control_bf16_state_fails"],
                "dropped_reader_cotangents": rows[0]["control_dropped_cotangents_fails"]}
    verdict = {"cell": CELL, "seeds": seeds, "device": jax.devices()[0].device_kind,
               "largest": largest, "limits": LIMITS, "program_passes": all(r["passes"] for r in rows),
               "controls_fail": controls,
               "ok": all(r["passes"] for r in rows) and all(controls.values())}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "phi4flash_chip_check.json"), "w") as f:
        json.dump({"rows": rows, "verdict": verdict}, f, indent=1)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

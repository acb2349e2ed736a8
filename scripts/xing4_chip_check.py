#!/usr/bin/env python3
"""Xing4.0-29B-A4B's cut at its published widths and the timed sizes on the chip, program against plain
reference, outside any timed window (the `model-configs` guide's section 3, item 3):

    chiprun -- python3 scripts/xing4_chip_check.py [--seed N] [--steps 2]

One seeded 4096-token sequence through the benchmark's own configuration
(benchmarks/configs/xing4.0-29b-a4b-d5-e8-v8.json: 1 + 4 layers inside four residual streams, 8 of 64 experts
held, the leaves started where the file says) and the cell's own layout (one chip, `--checkpoint 1`, the routed
layers scanned, the stacks as the launch builds the step on this chip) against the float32 reference on the
same weights and batch. Every limit stands between two readings, the sound program's and a FAULT's or a
CONTROL's, and each of those must fail a limit:

- **the program as the cell runs it** (bf16 compute and streams), over `--steps` steps of Adam: at each step its
  loss beside the reference's (the cell's own limit, through the harness's comparison), at the first step every
  leaf's gradient, relative by the Frobenius norm: the worst leaf of all, the worst `phi`, and the halves' `a` and
  `b` (3 + 24 numbers a half, each ONE signed sum over all tokens) as ONE vector over all ten halves, so that an
  entry that cancels to nothing is held to the size of the others and not to its own; then an Adam update on the
  host from the program's gradients, so the next comparison is on weights that are no longer the start;
- **the program in float32** (`compute_dtype` float32 at `highest` matmul precision, the same scan and
  recomputation, XLA's attention on the first 1024 tokens of the sequence: jax's flash kernels at 256 do not fit
  VMEM in float32): every leaf's gradient BY ITSELF, `a` and `b` among them. What is left of the bf16 readings here is
  their cause: rounding and the picks it flips, not the mixes' backward;
- **two faults planted in the write**: in the float32 program H_res TRANSPOSED (`X'[i] = sum_j H_res[j, i] X[j]`,
  a doubly-stochastic matrix still, the slip an index order makes; H_res lies near the identity for most tokens,
  so bf16 rounding would hide it: the float32 limit must refuse it), and in the program as the cell runs it the
  half's output written back UNGATED (`H_res X + o`, H_post taken for 1), which every bf16 gradient limit must refuse;
- **the one-stream control**: the program at `hc_mult` 1 on the same weights (the hyper-connections' leaves
  unread: `x + F(norm x)`), which the LOSS's limit must refuse: what says that the cell's `correct` sees the mixes;
- **the bf16 control**: the coefficients with their arithmetic in the next lower precision (`x~ Phi` as ONE pass
  on Phi rounded to bfloat16, every Sinkhorn step's result rounded to 8 bits of mantissa with
  `jax.lax.reduce_precision`: a `.astype` pair is taken out by the TPU compiler), on the same streams against the
  same formulas in float64 on the host (`coef_same_streams`, which it must fail), and the whole step's loss with
  that arithmetic patched in, which the loss's limit need not tell apart (the streams themselves are bf16);
- and how far each `switch_off` of the reference moves the reference's own loss.

The coefficients themselves, on the first and on the last weights: `models/parts/hyper.coefficients` as the step
runs it on the streams that enter layer 0 (the widened embedding) and the LAST layer, against float64 on the SAME
streams: the largest error of any token's H_pre, H_post or H_res entry, the rows' sums of H_res (1 by
construction) and its columns' (`hc_res_col_err`, which the step reports).

Writes `chiprun_out/xing4_chip_check_seed<N>.json`; its LAST line of output is the verdict with each measure's
largest reading beside its limit; exits 1 unless the program passes at every step and the faults and both
controls fail. Refuses to run where jax finds no TPU. About 17 min, and ALONE in its call: each of its fifteen
compiled programs leaves some 5 GiB with the process (a `stage` line says the host's memory after each part), the
peak stood at 43 GiB (call 11) and the chip machine ends a command at 40: calls 8 and 9 lost their second process so.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import resource
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "xing4-c1-s4k"
# measure -> most allowed. Each stands between two readings (my chip runs, PR 66, calls 8 and 11, seeds 66 / 67;
# PERF.md section 6): the largest the sound program gave over the steps, and what the fault or control named
# gave; near the geometric middle of the two where they lie decades apart.
LIMITS = {
    # the cell's own `checks.reference_loss.abs`, read from the file. Sound 5.0e-4 / 1.07e-3 at the first step and
    # 1.22e-3 / 8.6e-4 after an Adam step (at most 1.30e-3 on eighteen more seeds through the harness and the
    # sweep); the one-stream control 8.4e-3 / 1.66e-2, the ungated write 8.6e-3 / 1.71e-2; the bf16 control 2.1e-4 /
    # 1.9e-4, INSIDE the scatter: the loss does not tell a bf16 coefficient from a float32 one (the streams are
    # bf16 themselves), `coef_same_streams` does
    "loss": None,
    # bf16 program, any leaf but the halves' a / b: sound 0.266 / 0.304 (a routed layer's router kernel: its gradient
    # comes through the 8 held experts alone and the picks flip between bf16 and float32; the median leaf 0.085 /
    # 0.095); the ungated write 1.12 / 1.12
    "worst_leaf": 0.55,
    # bf16 program, the halves' phi: sound 0.075 to 0.130 / 0.082 to 0.117 over the ten; the ungated write 1.00 / 1.00
    "hc_leaf": 0.36,
    # bf16 program, the halves' a and b as ONE vector: sound 0.142 / 0.110 (by leaf 0.015 to 0.284); the ungated
    # write 0.99 / 1.04
    "hc_ab": 0.37,
    # float32 program (1024 tokens), every leaf by itself, a and b among them: sound 2.6e-4 / 6.2e-4 (an `a` leaf of
    # layer 0; the median leaf 1.9e-5 / 2.2e-5): what is left of the bf16 readings is rounding's; H_res transposed
    # 0.81 / 3.65
    "leaf_float32": 0.015,
    # any H entry against float64 on the same streams: sound 2.2e-6 to 3.5e-6 on the first and on the last weights
    # (the chip's float32 exp, sigmoid and 40 divisions); the bf16 control 7.3e-3 to 8.8e-3
    "coef_same_streams": 2e-5,
    # |a row's sum of H_res - 1|: `hc_eps` 1e-6 in the last division's denominator and its rounding, 1.24e-6 to 1.29e-6
    "res_row_err": 5e-6,
}
BF16_GRADS = ("worst_leaf", "hc_leaf", "hc_ab")
FLOAT32_TOKENS = 1024
V5E_BYTES = int(15.75 * 2 ** 30)  # where the device does not say what it holds
ADAM = dict(lr=3e-4, b1=0.9, b2=0.999, eps=1e-8)
HC_LEAVES = ("'hc1'", "'hc2'")
SWITCHES = ("x_scale", "sinkhorn_order", "clamp", "yarn_mscale", "sum_out", "hyper")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=66)
    parser.add_argument("--steps", type=int, default=2)
    parser.add_argument("--tiny", action="store_true", help="toy widths on any backend: the script's plumbing, no verdict")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu" and not args.tiny:
        print("xing4_chip_check needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    from benchmarks import cells
    from galvatron_tpu import HybridParallelConfig
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import hyper
    from galvatron_tpu.runtime import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.model_api import device_memory_limit, scan_stacks_are_tight
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    cell = cells.load_cell(ROOT, CELL)
    LIMITS["loss"] = cell.config["checks"]["reference_loss"]["abs"]
    ref = cells.load_module(ROOT, "benchmarks/references/%s.py" % cell.config["reference"])
    build = cells.import_attr(cell.config["program"]["config_fn"])
    seq = 256 if args.tiny else cell.traffic["seq_length"]
    tiny = dict(hidden_size=256, num_heads=4, num_kv_heads=4, ffn_hidden=128, dense_ffn_hidden=256, vocab_size=512,
                q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, head_dim=128,
                num_layers=3, attention_multiplier=48 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) if args.tiny else {}
    cfg = build(cell.config["program"]["preset"],
                **{**cell.fields, **tiny, "max_seq_len": seq, "compute_dtype": jnp.bfloat16})
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=1, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp)
    limit = device_memory_limit(jax.devices()[0])
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=ADAM["lr"], warmup_steps=0, total_steps=100))
    hp.narrow_scan_grads = scan_stacks_are_tight(model, tx, limit or V5E_BYTES)  # (as the launch asks, with its optimizer)
    print(json.dumps({"device_bytes_limit": limit, "narrow_scan_grads": hp.narrow_scan_grads}), flush=True)
    n, positions = cfg.hc_mult, jnp.arange(seq)[None]

    def rel(d, e):
        return float(np.linalg.norm(np.asarray(d, np.float64)) / max(np.linalg.norm(np.asarray(e, np.float64)), 1e-300))

    def leaf_errors(grads, ref_grads):
        return {jax.tree_util.keystr(path): rel(np.asarray(g, np.float64) - np.asarray(r, np.float64), r)
                for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                                        jax.tree_util.tree_leaves(ref_grads))
                if "e_score_correction_bias" not in jax.tree_util.keystr(path)}  # (takes no gradient)

    params = model.init_params(jax.random.PRNGKey(args.seed))
    tokens = jax.random.randint(jax.random.PRNGKey(args.seed + 1), (1, seq), 0, cfg.vocab_size)
    batch = model.shard_batch(dict(
        tokens=tokens, positions=positions, labels=jnp.roll(tokens, -1, 1),
        loss_mask=jnp.ones((1, seq), jnp.float32).at[:, -1].set(0.0)))

    def transposed_write(mix, x, o):  # the planted fault: H_res[j, i] where the write wants H_res[i, j]
        return own_write(mix._replace(res=[list(column) for column in zip(*mix.res)]), x, o)

    def ungated_write(mix, x, o):  # the gross fault: 1 where the write wants H_post, `H_res X + o`
        return own_write(mix._replace(post=[jnp.ones_like(h) for h in mix.post]), x, o)

    own_write = hyper.write

    def step_of(model, patched=None, highest=False, batch=batch):
        """value_and_grad of `model`'s loss on `batch`'s shapes, compiled with `patched` (name -> function) in
        the place of `models/parts/hyper`'s own; `highest`: float32 matmuls as float32."""
        own = {name: getattr(hyper, name) for name in (patched or {})}
        for name, fn in (patched or {}).items():
            setattr(hyper, name, fn)
        try:
            with jax.default_matmul_precision("highest") if highest else contextlib.nullcontext():
                lowered = jax.jit(jax.value_and_grad(model.loss_parts_fn, has_aux=True)).lower(params, batch)
                return lowered.compile(), lowered.as_text().count("tpu_custom_call")
        finally:
            for name, fn in own.items():
                setattr(hyper, name, fn)

    # float32 compute: jax's flash kernels at 256 do not fit VMEM in float32 (the first form of this script, call 7),
    # so XLA's attention, on the first FLOAT32_TOKENS tokens of the same sequence (its scores are 2 GB a layer at 4096)
    float32 = construct_hybrid_parallel_model(dataclasses.replace(cfg, compute_dtype=jnp.float32, attn_impl="xla"), hp)
    short = {k: v[:, :FLOAT32_TOKENS] for k, v in batch.items()}
    short["loss_mask"] = short["loss_mask"].at[:, -1].set(0.0)
    one_stream = construct_hybrid_parallel_model(
        dataclasses.replace(cfg, hc_mult=1, hc_sinkhorn_iters=0, hc_res_clamp=None), hp)
    step, kernels = step_of(model)
    reference_step = jax.jit(jax.value_and_grad(lambda p, b: ref.loss(p, b, fields)))
    reference_loss = jax.jit(lambda p, b: ref.loss(p, b, fields))

    # ---------------------------------------------------------- the coefficients themselves
    kinds = cfg.layer_kinds()

    @jax.jit
    def streams_entering(p, b):
        """(the streams entering layer 0, the streams entering the last layer), as the step makes them."""
        first = hyper.widen(M.embed_tokens(p["embed"], b["tokens"], b["positions"], cfg, None, None), n)
        x = first
        for i in range(cfg.num_layers - 1):
            x = M.layer_forward(p["layers"][i], x, b["positions"], cfg.layer_config(kinds[i]))[0]
        return first, x

    def rounded(x):  # bfloat16's 8 bits of mantissa, kept by the compiler
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def sinkhorn_bf16(m, iters, eps):
        for _ in range(iters):
            m = rounded(m / (sum(m[i] for i in range(n))[None] + eps))
            m = rounded(m / (sum(m[:, j] for j in range(n))[:, None] + eps))
        return m

    def one_pass_dot(x, phi):  # `x~ Phi` as ONE bf16 pass of the MXU, float32 sums: Phi rounded to bfloat16
        return jnp.dot(x.astype(jnp.bfloat16), phi.astype(jnp.bfloat16), preferred_element_type=jnp.float32)

    def as_arrays(mix):
        return (jnp.concatenate(mix.pre, -1), jnp.concatenate(mix.post, -1),
                jnp.stack([jnp.concatenate(row, -1) for row in mix.res], -2))

    coefficients = jax.jit(lambda leaf, x: as_arrays(hyper.coefficients(leaf, x, cfg)[0]))

    def coefficients_bf16(leaf, x):
        own = hyper.streams_dot, hyper.sinkhorn
        hyper.streams_dot, hyper.sinkhorn = one_pass_dot, sinkhorn_bf16
        try:
            return jax.jit(lambda leaf, x: as_arrays(hyper.coefficients(leaf, x, cfg)[0]))(leaf, x)
        finally:
            hyper.streams_dot, hyper.sinkhorn = own

    def coefficients_float64(leaf, x):
        flat = np.asarray(x.astype(jnp.float32), np.float64).reshape(seq, -1)
        flat = flat / np.sqrt(np.mean(flat * flat, axis=-1, keepdims=True) + cfg.hc_eps)
        pqr = flat @ np.asarray(leaf["phi"], np.float64)
        a, b = np.asarray(leaf["a"], np.float64), np.asarray(leaf["b"], np.float64)
        sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
        m = np.exp(np.clip(a[2] * pqr[:, 2 * n:].reshape(seq, n, n) + b[2 * n:].reshape(n, n), *cfg.hc_res_clamp))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (m.sum(axis=1, keepdims=True) + cfg.hc_eps)
            m = m / (m.sum(axis=2, keepdims=True) + cfg.hc_eps)
        return sig(a[0] * pqr[:, :n] + b[:n]), 2.0 * sig(a[1] * pqr[:, n:2 * n] + b[n:2 * n]), m

    def coefficient_errors(params):
        out = {}
        for where, x, layer in zip(("layer0", "last_layer"), streams_entering(params, batch), (0, cfg.num_layers - 1)):
            for half in ("hc1", "hc2"):
                leaf = params["layers"][layer][half]
                want = coefficients_float64(jax.device_get(leaf), x)
                for name, fn in (("program", coefficients), ("control_bf16", coefficients_bf16)):
                    got = [np.asarray(t, np.float64).reshape(w.shape) for t, w in zip(fn(leaf, x), want)]
                    out.setdefault(name, {})["%s.%s" % (where, half)] = max(
                        float(np.max(np.abs(g - w))) for g, w in zip(got, want))
                    if name == "program":
                        out.setdefault("res_row_err", {})["%s.%s" % (where, half)] = float(
                            np.max(np.abs(got[2].sum(axis=-1) - 1.0)))
                        out.setdefault("res_col_err", {})["%s.%s" % (where, half)] = float(
                            np.max(np.abs(got[2].sum(axis=-2) - 1.0)))
                out.setdefault("res_off_identity_float64", {})["%s.%s" % (where, half)] = float(
                    np.max(np.abs(want[2] - np.eye(n))))
        return out

    # ------------------------------------------------------------------ the steps
    def let_go(stage):
        """The stage's compiled programs and host copies released, and the host memory said (the machine ends a
        command at 40 GiB: call 8 and call 9 of PR 66 ended so)."""
        jax.clear_caches()
        gc.collect()
        with open("/proc/self/status") as f:
            now = [int(line.split()[1]) for line in f if line.startswith("VmRSS")]  # (kB; a sandbox may not say)
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        print(json.dumps({"stage": stage, "host_rss_gib": round(now[0] / 2 ** 20, 2) if now else None,
                          "host_peak_gib": round(peak / 2 ** 20, 2)}), flush=True)

    rows, moments = [], None
    first = coefficient_errors(params)
    let_go("coefficients on the first weights")
    print(json.dumps({"coefficients_on_the_same_streams": first}), flush=True)
    for i in range(args.steps):
        (loss, parts), grads = step(params, batch)
        ref_loss = reference_loss(params, batch)
        row = {"step": i, "loss": float(loss), "reference_loss": float(ref_loss), "kernels_in_step": kernels,
               "parts": {k: float(v) for k, v in parts.items() if np.ndim(v) == 0},
               "measures": {"loss": abs(float(loss) - float(ref_loss))}}
        grads = jax.device_get(grads)
        if i == 0:
            ref_grads = jax.device_get(reference_step(params, batch)[1])

            def gradient_measures(grads, ref_grads):
                """-> (the bf16 limits' measures, every leaf's error). A half's `a` and `b` are held as ONE vector
                over all halves: each entry is a signed sum over all tokens, and one that cancels to nothing
                would otherwise be held to its own size."""
                every = leaf_errors(grads, ref_grads)
                gates = [k for k in every if any(word in k for word in HC_LEAVES) and k[-5:] in ("['a']", "['b']")]
                got, want = ({k: np.asarray(g, np.float64).reshape(-1) for k, g in (
                    (jax.tree_util.keystr(path), g) for path, g in jax.tree_util.tree_flatten_with_path(t)[0]) if k in gates}
                    for t in (grads, ref_grads))  # (the gates alone: a float64 copy of every leaf is 6 GB a tree)
                ab = rel(np.concatenate([got[k] - want[k] for k in gates]), np.concatenate([want[k] for k in gates]))
                leaves = {k: v for k, v in every.items() if k not in gates}
                phi = {k: v for k, v in leaves.items() if any(word in k for word in HC_LEAVES)}
                return ({"worst_leaf": max(leaves.values()), "hc_leaf": max(phi.values()), "hc_ab": ab}, every,
                        {"worst_leaf_name": max(leaves, key=leaves.get), "worst_hc_leaf_name": max(phi, key=phi.get),
                         "median_leaf": float(np.median(list(every.values()))),
                         "hc_ab_leaves_worst": max(every[k] for k in gates)})

            measures, every, said = gradient_measures(grads, ref_grads)
            row.update({**said, "leaves": {k: round(v, 5) for k, v in every.items()}})
            row["measures"].update({**measures, "coef_same_streams": max(first["program"].values()),
                                    "res_row_err": max(first["res_row_err"].values())})
            # the gross fault as the cell runs it: the half's output written back ungated
            (fault_loss, _), fault_grads = step_of(model, {"write": ungated_write})[0](params, batch)
            row["fault_post_ungated"] = {"loss": abs(float(fault_loss) - float(ref_loss)),
                                         **gradient_measures(jax.device_get(fault_grads), ref_grads)[0]}
            del fault_grads, ref_grads
            let_go("the program and the ungated write against the reference, bf16")
            # the program in float32: every leaf by itself; what is left of the bf16 readings is rounding's
            ref_loss32, ref_grads32 = jax.device_get(reference_step(params, short))
            (loss32, _), grads32 = step_of(float32, highest=True, batch=short)[0](params, short)
            every32 = gradient_measures(jax.device_get(grads32), ref_grads32)[1]
            row["float32"] = {"tokens": FLOAT32_TOKENS, "loss": abs(float(loss32) - float(ref_loss32)),
                              "worst_leaf_name": max(every32, key=every32.get),
                              "leaves": {k: round(v, 6) for k, v in every32.items()}}
            row["measures"]["leaf_float32"] = max(every32.values())
            (_, _), grads32 = step_of(float32, {"write": transposed_write}, highest=True, batch=short)[0](params, short)
            row["fault_res_transposed"] = {
                "leaf_float32": max(gradient_measures(jax.device_get(grads32), ref_grads32)[1].values())}
            row["fault_fails"] = {name: [k for k, v in row[name].items() if v > LIMITS[k]]
                                  for name in ("fault_res_transposed", "fault_post_ungated")}
            del grads32, ref_grads32
            let_go("the program and the transposed write in float32")
            # the one-stream control: the hyper-connections' leaves unread, the plain residual
            plain = {**params, "layers": [{k: v for k, v in lp.items() if k not in hyper.LEAVES.values()}
                                          for lp in params["layers"]]}
            plain_loss = jax.jit(lambda p, b: one_stream.loss_parts_fn(p, b)[0])(plain, batch)
            row["control_one_stream"] = {"loss": abs(float(plain_loss) - float(ref_loss))}
            # what the loss's limit can tell: how far each other candidate of an assumed form moves the reference
            row["switch_moves_the_reference_loss_by"] = {
                off: abs(float(jax.jit(lambda p, b, off=off: ref.loss(p, b, fields, switch_off=(off,)))(params, batch))
                         - float(ref_loss)) for off in SWITCHES}
            # the bf16 control: the whole step's loss with the coefficients' arithmetic in bfloat16, and the
            # coefficients themselves
            own = hyper.streams_dot, hyper.sinkhorn
            hyper.streams_dot, hyper.sinkhorn = one_pass_dot, sinkhorn_bf16
            try:
                control_loss = float(jax.jit(lambda p, b: model.loss_parts_fn(p, b)[0])(params, batch))
            finally:
                hyper.streams_dot, hyper.sinkhorn = own
            row["control_bf16"] = {"loss": abs(control_loss - float(ref_loss)),
                                   "coef_same_streams": max(first["control_bf16"].values())}
            row["control_fails"] = {name: [k for k, v in row[name].items() if v > LIMITS[k]]
                                    for name in ("control_one_stream", "control_bf16")}
        row["passes"] = all(v <= LIMITS[k] for k, v in row["measures"].items())
        rows.append(row)
        print(json.dumps(row), flush=True)
        # Adam on the host, from the PROGRAM's gradients: the next step's weights are the timed path's own
        host = jax.device_get(params)
        if moments is None:
            moments = jax.tree.map(lambda p: (np.zeros_like(p), np.zeros_like(p)), host)
        t = i + 1

        def update(p, g, mv):
            m, v = mv
            m *= ADAM["b1"]
            m += (1 - ADAM["b1"]) * g
            v *= ADAM["b2"]
            v += (1 - ADAM["b2"]) * g * g
            return p - ADAM["lr"] * (m / (1 - ADAM["b1"] ** t)) / (np.sqrt(v / (1 - ADAM["b2"] ** t)) + ADAM["eps"])

        host = jax.tree.map(update, host, jax.tree.map(lambda g: np.asarray(g, np.float32), grads), moments,
                            is_leaf=lambda x: isinstance(x, tuple))
        del grads
        params = jax.device_put(host, jax.tree.map(lambda a: a.sharding, params))
        del host
        let_go("step %d and its update" % i)
    last = coefficient_errors(params)
    print(json.dumps({"coefficients_on_the_last_weights": last}), flush=True)
    largest = {k: max(r["measures"][k] for r in rows if k in r["measures"]) for k in LIMITS}
    largest["coef_same_streams"] = max(largest["coef_same_streams"], *last["program"].values())
    largest["res_row_err"] = max(largest["res_row_err"], *last["res_row_err"].values())
    passes = all(r["passes"] for r in rows) and all(largest[k] <= LIMITS[k] for k in LIMITS)
    fails_last = max(last["control_bf16"].values()) > LIMITS["coef_same_streams"]
    verdict = {"cell": CELL, "seed": args.seed, "steps": args.steps, "device": jax.devices()[0].device_kind,
               "largest": largest, "limits": LIMITS, "program_passes": passes,
               "res_col_err": max(*first["res_col_err"].values(), *last["res_col_err"].values()),
               "float32_loss": rows[0]["float32"]["loss"], "fault_res_transposed": rows[0]["fault_res_transposed"],
               "fault_post_ungated": rows[0]["fault_post_ungated"], "fault_fails": rows[0]["fault_fails"],
               "control_one_stream": rows[0]["control_one_stream"],
               "control_bf16": rows[0]["control_bf16"], "control_fails": rows[0]["control_fails"],
               "control_bf16_on_the_last_weights": max(last["control_bf16"].values()),
               "control_fails_on_the_last_weights": fails_last,
               "ok": passes and fails_last and set(BF16_GRADS) <= set(rows[0]["fault_fails"]["fault_post_ungated"])
               and "leaf_float32" in rows[0]["fault_fails"]["fault_res_transposed"]
               and "loss" in rows[0]["control_fails"]["control_one_stream"]
               and "coef_same_streams" in rows[0]["control_fails"]["control_bf16"]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "xing4_chip_check_seed%d.json" % args.seed), "w") as f:
        json.dump({"rows": rows, "first": first, "last": last, "verdict": verdict}, f, indent=1)
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

"""On the chip: is a cell's step read through `parts/mlp.grad_as_stored` the
compiler's own step in VALUES? (PR 48)

    chiprun --timeout 1500 -- bash -c "python3 scripts/gated_grad_chip_check.py granite4h-c1-s4k on && \\
        python3 scripts/gated_grad_chip_check.py granite4h-c1-s4k off && \\
        python3 scripts/gated_grad_chip_check.py granite4h-c1-s4k compare"

`on` / `off` build the benchmark's cell as its trainer does (the cell's flags,
its size, its optimizer but for the clipping: `--clip_grad` is set out of
reach, so that nothing but a leaf's OWN gradient reaches its update; with the
trainer's clipping the global norm, a sum over every gradient that a fusion
reading another tiling adds up in another order, scales every update and the
two runs part at float32's rounding), run STEPS steps from the seed's state on
the seed's batches, with the rule (`models/base._gated_grads_as_stored`) or
with the layers left to the compiler, and write every leaf's SHA-1 (the
parameters and Adam's moments) under chiprun_out/gated_grad_chip_check/.
`compare` (no jax) holds the two against each other: the rule is right if no
leaf differs. One process a form: a chip holds one such state at a time. A
fourth word keeps more of the trainer's own step instead: `clip` its clipping,
`guard` its anomaly guard (`--anomaly_guard`: the step with the keep-old
select), `as_trained` both; a fifth the number of steps.

`on` also says what the compiler does with the rounding the jaxpr states for
a bf16 matmul whose result a fused `convert` widens at once (`fused_convert`:
the scanned body's `bf16 convolution -> convert -> dynamic-update-slice`
fusion, against the same product accumulated and kept in float32)."""

from __future__ import annotations

import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join("chiprun_out", "gated_grad_chip_check")
STEPS, SEED = 4, 1790004801


def leaves_after_steps(workload: str, rule: bool, keep: str, steps: int) -> dict:
    import jax
    import numpy as np

    from benchmarks import cells
    from galvatron_tpu.cli.arguments import hp_config_from_args, initialize_galvatron, model_config_from_args
    from galvatron_tpu.cli.train import build_data_iterator, optimizer_args_from
    from galvatron_tpu.models import base as M
    from galvatron_tpu.obs import forms
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import get_optimizer_and_scheduler

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU here: this check reads what the chip's compiler does")
    if not rule:
        M._gated_grads_as_stored = lambda layers, *a, **k: layers
    cell = cells.load_cell(ROOT, workload)
    cells.register_family(cell)
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, SEED)
                                + ([] if keep in ("clip", "as_trained") else ["--clip_grad", "1e30"]))
    guard = keep in ("guard", "as_trained") and bool(getattr(args, "anomaly_guard", 0))
    fam, cfg = model_config_from_args(args)
    hp = hp_config_from_args(args, cfg.num_layers, cell.chips)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:cell.chips])
    tx, _ = get_optimizer_and_scheduler(optimizer_args_from(args))
    params = model.init_params(jax.random.PRNGKey(args.seed))
    opt = model.init_opt_state(tx, params)
    data = build_data_iterator(args, fam, cfg, hp)
    step = model.make_train_step(tx, guard_anomalies=guard)
    cap = (np.float32(np.inf),) if guard else ()
    losses, norms = [], []
    with forms.recording() as took:
        for _ in range(steps):
            params, opt, metrics = step(params, opt, model.shard_batch(next(data)), *cap)
            losses.append(float(metrics["loss"]))
            norms.append(float(metrics["grad_norm"]))
    return {"workload": workload, "rule": rule, "steps": steps, "clip_grad": args.clip_grad, "guard": guard,
            "kernels_relaid": took[forms.GATED_KERNEL_GRADS]["as_stored"],
            "losses": losses, "grad_norms": norms,
            "leaves": {jax.tree_util.keystr(path): hashlib.sha1(np.asarray(leaf).tobytes()).hexdigest()
                       for path, leaf in jax.tree_util.tree_leaves_with_path((params, opt))}}


def fused_convert() -> dict:
    """A bf16 x bf16 matmul whose bf16 result is widened and written into a
    float32 stack in one fusion, as a scanned layer's backward does: does the
    chip round the product to bf16 on the way (the jaxpr's values), or keep
    the float32 accumulator (`xla_allow_excess_precision`)?"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    h, f, s = 1024, 4096, 2048
    y = jax.random.normal(jax.random.PRNGKey(2), (s, h), jnp.bfloat16)
    d = jax.random.normal(jax.random.PRNGKey(3), (s, 2, f), jnp.bfloat16)

    def stacked(buf, y, d, i):
        g = jnp.einsum("sh,scf->hcf", y, d)  # bf16, as the cast kernel's cotangent
        return jax.lax.dynamic_update_slice(buf, g.astype(jnp.float32)[None], (i, 0, 0, 0))

    fn = jax.jit(stacked, donate_argnums=0).lower(
        jax.ShapeDtypeStruct((2, h, 2, f), jnp.float32), y, d, jnp.int32(1)).compile()
    got = np.asarray(fn(jnp.zeros((2, h, 2, f), jnp.float32), y, d, jnp.int32(1)))[1]
    wide = np.asarray(jax.jit(lambda y, d: jnp.einsum("sh,scf->hcf", y, d, preferred_element_type=jnp.float32))(y, d))
    rounded = np.asarray(jnp.asarray(wide).astype(jnp.bfloat16).astype(jnp.float32))
    return {"convolutions": re.findall(r"= (\w+\[[\d,]*\])\{[^ ]*\} convolution\(", fn.as_text()),
            "equals_the_float32_product": bool(np.array_equal(got, wide)),
            "equals_the_product_rounded_to_bf16": bool(np.array_equal(got, rounded))}


def compare(workload: str) -> dict:
    on, off = (json.load(open(os.path.join(OUT, "%s.%s.json" % (workload, form)))) for form in ("on", "off"))
    apart = sorted(k for k in on["leaves"] if on["leaves"][k] != off["leaves"][k])
    return {"workload": workload, "steps": [on["steps"], off["steps"]], "kernels_relaid": [on["kernels_relaid"], off["kernels_relaid"]],
            "losses": [on["losses"], off["losses"]], "grad_norms": [on["grad_norms"], off["grad_norms"]],
            "clip_grad": [on["clip_grad"], off["clip_grad"]], "guard": [on["guard"], off["guard"]],
            "leaves": len(on["leaves"]), "leaves_apart": len(apart), "first_apart": apart[:12],
            "fused_convert": on.get("fused_convert"),
            "verdict": ("the rule's step is the compiler's own to the bit" if not apart and on["kernels_relaid"]
                        else "the rule's step differs in values" if apart else "the rule did not engage")}


if __name__ == "__main__":
    workload, form = sys.argv[1:3]
    os.makedirs(OUT, exist_ok=True)
    if form == "compare":
        said = compare(workload)
    else:
        said = leaves_after_steps(workload, form == "on", (sys.argv[3:4] or [""])[0], int((sys.argv[4:5] or [STEPS])[0]))
        if form == "on":
            said["fused_convert"] = fused_convert()
    with open(os.path.join(OUT, "%s.%s.json" % (workload, form)), "w") as out:
        json.dump(said, out, indent=1)
    print(json.dumps({k: v for k, v in said.items() if k != "leaves"}))

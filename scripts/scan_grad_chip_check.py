"""On the chip: is a cell's gradient with the scanned runs' cotangents asked
for in ZeRO's layout (PR 55, `models/base.run_layers`) the gradient of the
step that asked for them whole over dp, in VALUES?

    chiprun --chips 4 -- python3 scripts/scan_grad_chip_check.py qwen7-c4-tp2dp2

Builds the benchmark's cell as its trainer does (the cell's flags and size),
and in ONE process differentiates the model's own loss twice on the seed's
state and one batch: as the model hands it over (`zero_splits_state` as
`model._zero_splits_state()` says) and with that answer forced to False, which
is the program before PR 55 (the plain constraint on a run's stacked leaves,
the stacked gradient whole over dp and sliced by `to_accum`). Both gradients
are widened and laid out as the step accumulates them. A line of JSON a
leaf that differs (elements that differ, the largest difference in steps of
the compute dtype at the leaf's largest gradient), then the verdict: the two
losses, how many leaves are equal to the bit, and `ok` where every leaf is
within one such step. The two are the same sums of the same bf16 partial
products; what may differ is how the compiler partitions a matmul around
them (its float32 accumulation order), never by more than the last bit."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
SEED = 1790005501


def main(workload: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import cells
    from galvatron_tpu.cli.arguments import hp_config_from_args, initialize_galvatron, model_config_from_args
    from galvatron_tpu.models import base as M
    from galvatron_tpu.obs import forms
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU here: this check reads what the chip's compiler does")
    cell = cells.load_cell(ROOT, workload)
    cells.register_family(cell)
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, SEED))
    _, cfg = model_config_from_args(args)
    hp = hp_config_from_args(args, cfg.num_layers, cell.chips)
    model = construct_hybrid_parallel_model(cfg, hp)
    assert model._zero_splits_state(), "this layout asks for nothing: its step is the parent's"
    rows, seq = cell.traffic["global_batch"], cell.traffic["seq_length"]
    tokens = jax.random.randint(jax.random.PRNGKey(SEED), (rows, seq), 0, cfg.vocab_size)
    batch = model.shard_batch(dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(seq), (rows, seq)),
                                   labels=jnp.roll(tokens, -1, 1)))
    params = model.init_params(jax.random.PRNGKey(SEED))
    accum = model.shardings(model.grad_accum_specs())

    def gradient(asked: bool):
        def loss(p, b):
            return M.lm_loss_fn(p, b, cfg, hp, model.mesh, table_spec=model.table_spec(), zero_splits_state=asked)

        def widened(p, b):
            value, grads = jax.value_and_grad(loss)(model.compute_params(p), b)
            return value, jax.tree.map(lambda g, leaf, s: jax.lax.with_sharding_constraint(g.astype(leaf.dtype), s),
                                       grads, p, accum)

        with forms.recording() as took:
            value, grads = jax.jit(widened)(params, batch)
        return float(value), grads, took[forms.SCAN_GRADS]["zero_layout"]

    loss_asked, asked, leaves_asked = gradient(True)
    loss_plain, plain, leaves_plain = gradient(False)
    assert leaves_asked > 0 and leaves_plain == 0, (leaves_asked, leaves_plain)
    bits = jnp.finfo(cfg.compute_dtype).nmant
    equal, worst = 0, 0.0
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(asked)]
    for path, a, b in zip(paths, jax.tree.leaves(asked), jax.tree.leaves(plain)):
        differ = int(jnp.sum(a != b))
        if not differ:
            equal += 1
            continue
        one_step = 2.0 ** (np.floor(np.log2(float(jnp.max(jnp.abs(b))))) - bits)
        steps = float(jnp.max(jnp.abs(a - b))) / one_step
        worst = max(worst, steps)
        print(json.dumps({"leaf": path, "elements": int(a.size), "differ": differ, "largest_in_steps": steps}), flush=True)
    print(json.dumps({"workload": workload, "loss_asked": loss_asked, "loss_plain": loss_plain,
                      "leaves_asked_in_zero_layout": leaves_asked, "leaves": len(paths), "leaves_equal_to_the_bit": equal,
                      "largest_in_steps": worst, "ok": bool(loss_asked == loss_plain and worst <= 1.0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

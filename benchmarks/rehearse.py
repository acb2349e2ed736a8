#!/usr/bin/env python3
"""Compile-only rehearsal of a cell for a DESCRIBED TPU v5e 2x2, no chip
attached: what the chip's compiler would refuse (a step or a reference that
does not fit, a kernel it cannot tile or partition) is refused here, at no
chip time. Nothing runs and nothing here is a measurement.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py <workload> [<workload> ...]

Prints, a cell, the compiled step's bytes a chip (what `step_hbm_gib` will
read), whether the flash kernel and the layout's collectives are in it, and
the plain reference's bytes a chip.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rehearse(workload: str, topo_devices) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from benchmarks import cells, harness, stages
    from galvatron_tpu.cli.arguments import (hp_config_from_args, initialize_galvatron,
                                             model_config_from_args)
    from galvatron_tpu.cli.train import optimizer_args_from
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import get_optimizer_and_scheduler

    cell = cells.load_cell(ROOT, workload)
    cells.register_family(cell)
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, 0))
    _, cfg = model_config_from_args(args)
    hp = hp_config_from_args(args, cfg.num_layers, cell.chips)
    model = construct_hybrid_parallel_model(cfg, hp, topo_devices[:cell.chips])
    tx, _ = get_optimizer_and_scheduler(optimizer_args_from(args))

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    params = model.abstract_params()
    opt = jax.eval_shape(tx.init, params)
    shape = (cell.traffic["global_batch"], cell.traffic["seq_length"])
    batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
        model.mesh, model._batch_spec_for(jax.ShapeDtypeStruct(shape, dt))))
        for k, dt in (("tokens", jnp.int32), ("positions", jnp.int32),
                      ("labels", jnp.int32), ("loss_mask", jnp.float32))}
    p_sds = sds(params, model.shardings())
    step = model.make_train_step(tx).lower(
        p_sds, sds(opt, model.opt_state_shardings(tx, params)), batch).compile()
    hlo = step.as_text()
    ref = cells.load_module(ROOT, "benchmarks/references/%s.py" % cell.config["reference"])
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    division = tuple(hp.pp_division)  # a pipelined tree is unstacked as the harness does it
    ref_mem = harness.step_memory(jax.jit(lambda p, b: ref.loss(
        stages.per_layer_tree(p, division), b, fields)).lower(p_sds, batch).compile())
    return {
        "workload": workload, "compile_only": True, **harness.step_memory(step),
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        "collectives": {c: hlo.count(c) for c in cell.collectives},
        "reference_hbm_gib": ref_mem["step_hbm_gib"],
    }


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for workload in argv:
        print(json.dumps(rehearse(workload, list(topo.devices))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

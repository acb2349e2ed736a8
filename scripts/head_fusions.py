#!/usr/bin/env python3
"""What the TPU compiler makes of the head and its loss, compile-only: every
operation of a cell's step under the `gt.head_loss` scope, for a DESCRIBED
v5e (no chip attached), with the shapes it reads, whether it holds a matmul
or an `exp`, and XLA's own cycle estimate. Not a benchmark cell and not a
measurement:

    JAX_PLATFORMS=cpu python3 scripts/head_fusions.py qwen7-c1-s2k [<cell> ...]

`estimated_cycles` / 1.5 GHz came within 1 to 7 % of the chip's time for the
matmul fusions and two to three times OVER it for elementwise passes (PERF.md,
PR 30): it ranks the matmuls and says what a fusion holds; it is no time.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCOPE = "gt.head_loss"
CLOCK_HZ = 1.5e9  # the clock `estimated_cycles` was checked against on a v5e

_INSTR = re.compile(r"^\s*(?:ROOT )?%(?P<name>[\w.-]+) = (?P<shape>\(.*?\)|\S+) (?P<code>[a-z][\w-]*)\(")
_SHAPE = re.compile(r"(?:pred|[a-z]+\d+)\[[\d,]*\]")


def computations(hlo: str) -> dict:
    """name -> the lines of that computation's body."""
    out, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def _opcodes(comps: dict, line: str) -> list:
    """The opcodes of the computation an instruction calls, nested fusions'
    included."""
    called = re.search(r"calls=%([\w.-]+)", line)
    out = []
    for inner in comps.get(called.group(1), []) if called else []:
        m = _INSTR.match(inner)
        if m:
            out += [m.group("code")] + _opcodes(comps, inner)
    return out


def head_ops(hlo: str, scope: str = SCOPE) -> list:
    """The operations outside fused computations whose `op_name` carries
    `scope`, in program order: name, opcode, what the op_name ends in, whether
    it is the backward (`transpose(`) and of it the recomputation
    (`rematted_computation`), its output and operand shapes, the opcodes of
    the fusion it calls, and XLA's cycle estimate."""
    comps = computations(hlo)
    shapes = {}
    for lines in comps.values():
        for line in lines:
            m = _INSTR.match(line)
            if m:
                shapes[m.group("name")] = m.group("shape")
    ops = []
    for comp, lines in comps.items():
        if comp.startswith("fused_computation"):
            continue
        for line in lines:
            m = _INSTR.match(line)
            op_name = re.search(r'op_name="([^"]*)"', line)
            if not m or not op_name or scope not in op_name.group(1):
                continue
            if m.group("code") in ("parameter", "constant", "get-tuple-element", "bitcast", "tuple"):
                continue
            args = line[m.end():].split("), ")[0]
            inner = _opcodes(comps, line)
            cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
            if not cycles and not any(re.search(r"\[\d", s) for s in _SHAPE.findall(m.group("shape"))):
                continue  # a reducer's scalar body
            ops.append({
                "name": m.group("name"), "code": m.group("code"),
                "op_name": op_name.group(1).rsplit(scope, 1)[1].lstrip("/)"),
                "backward": "transpose(" in op_name.group(1),
                "recomputed": "rematted_computation" in op_name.group(1),
                "out": _SHAPE.findall(m.group("shape")),
                "operands": [s for a in re.findall(r"%([\w.-]+)", args)
                             for s in _SHAPE.findall(shapes.get(a, ""))],
                "matmul": "convolution" in inner or m.group("code") in ("convolution", "dot"),
                "exp": inner.count("exponential") + (m.group("code") == "exponential"),
                "est_ms": round(int(cycles.group(1)) / CLOCK_HZ * 1e3, 3) if cycles else None,
            })
    return ops


def compiled_step(workload: str, topo_devices):
    """A cell's train step compiled for the described devices. A COPY of
    `benchmarks/rehearse.py`'s `rehearse`, from `cells.load_cell` to
    `.compile()` (its lines 35 to 57 at PR 30), which keeps only numbers of
    the compiled step and which this PR may not edit; a `benchmark` PR that
    has `rehearse.py` return the compiled step replaces this body by a call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from benchmarks import cells
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
    shape = (cell.traffic["global_batch"], cell.traffic["seq_length"])
    batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
        model.mesh, model._batch_spec_for(jax.ShapeDtypeStruct(shape, dt))))
        for k, dt in (("tokens", jnp.int32), ("positions", jnp.int32),
                      ("labels", jnp.int32), ("loss_mask", jnp.float32))}
    return model.make_train_step(tx).lower(
        sds(params, model.shardings()),
        sds(jax.eval_shape(tx.init, params), model.opt_state_shardings(tx, params)), batch).compile()


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies

    from benchmarks import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for workload in argv:
        step = compiled_step(workload, list(topo.devices))
        ops = head_ops(step.as_text())
        print(json.dumps({"workload": workload, "compile_only": True, **harness.step_memory(step),
                          "head_est_ms": round(sum(o["est_ms"] or 0 for o in ops), 3)}))
        for o in ops:
            print("  " + json.dumps(o))
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

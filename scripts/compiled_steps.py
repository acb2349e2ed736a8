#!/usr/bin/env python3
"""The cells' compiled steps of two checkouts, compared instruction for
instruction, with no chip: what a PR that adds a model shows of the cells it
must not move.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python3 scripts/compiled_steps.py dump <checkout> <out_dir> [<cell> ...]
    python3 scripts/compiled_steps.py diff <out_dir_a> <out_dir_b>
    python3 scripts/compiled_steps.py copies <out_dir> [<cell> ...]

`dump` compiles each cell's step (default: every cell of the checkout's
BENCHMARK.json) for a described v5e 2x2, as `benchmarks/rehearse.py` does, from
the checkout given (`git archive <parent> | tar -x -C _parent`; lay this PR's
benchmark files over it so that both sides read the same cells), and writes the
optimized HLO a cell. `diff` compares two such directories after taking out what
names the SOURCE and not the program: the tables of files, functions and
locations and the `stack_frame_id`s (a line added above a function moves
them), `metadata={...}`, the checkout's path, and the source locations inside
a Mosaic kernel's serialized body (the body is read back as MLIR and printed
without them: a kernel whose program changed differs, one whose file only
moved does not). Exit 1 where a cell differs. One process a checkout: the
program is imported from it. `copies` reads a dumped cell's relayouts: the
`copy` ops over 4 MB that stand alone (an instruction, or all a fusion does)
by dtype, shape and the layout copied INTO, their count and bytes read +
written, how many more are fused into their reader, and `memory_analysis()`'s
four sizes as `dump` left them in `<cell>.memory.json`: a line of JSON a cell."""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys


def dump(root: str, out: str, workloads) -> None:
    root = os.path.abspath(root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(root)
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from benchmarks import cells
    from galvatron_tpu.cli.arguments import hp_config_from_args, initialize_galvatron, model_config_from_args
    from galvatron_tpu.cli.train import optimizer_args_from
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import get_optimizer_and_scheduler

    topo = list(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)
    workloads = workloads or [w["name"] for w in cells.load_json(root, cells.MANIFEST)["workloads"]]
    os.makedirs(out, exist_ok=True)
    for workload in workloads:
        cell = cells.load_cell(root, workload)
        cells.register_family(cell)
        args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, 0))
        _, cfg = model_config_from_args(args)
        hp = hp_config_from_args(args, cfg.num_layers, cell.chips)
        model = construct_hybrid_parallel_model(cfg, hp, topo[:cell.chips])
        tx, _ = get_optimizer_and_scheduler(optimizer_args_from(args))
        try:  # the launch's own decision, at the memory a v5e reports (a described device reports none)
            from galvatron_tpu.runtime.model_api import scan_stacks_are_tight
            hp.narrow_scan_grads = scan_stacks_are_tight(model, tx, V5E_BYTES)
        except ImportError:  # a checkout from before PR 61
            pass

        def sds(tree, shardings):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

        params = model.abstract_params()
        shape = (cell.traffic["global_batch"], cell.traffic["seq_length"])
        batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
            model.mesh, model._batch_spec_for(jax.ShapeDtypeStruct(shape, dt))))
            for k, dt in (("tokens", jnp.int32), ("positions", jnp.int32),
                          ("labels", jnp.int32), ("loss_mask", jnp.float32))}
        step = model.make_train_step(tx).lower(
            sds(params, model.shardings()),
            sds(jax.eval_shape(tx.init, params), model.opt_state_shardings(tx, params)), batch).compile()
        with open(os.path.join(out, workload + ".hlo.txt"), "w") as f:
            f.write(step.as_text())
        memory = step.memory_analysis()
        with open(os.path.join(out, workload + ".memory.json"), "w") as f:
            json.dump({k: getattr(memory, k + "_size_in_bytes") for k in MEMORY_SIZES}, f)
        print(json.dumps({"workload": workload, "root": root, "instructions": step.as_text().count(" = ")}),
              flush=True)


MEMORY_SIZES = ("argument", "output", "alias", "temp")
V5E_BYTES = 16909336064  # `memory_stats()["bytes_limit"]` of a v5e (my chip run, PR 61)
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
            "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
COPY_OVER = 4 << 20  # bytes of the array a `copy` has to move to be listed
# `%copy.1 = f32[2048,2,8192]{2,0,1:T(8,128)} copy(%param.1), ...`: the layout is the one copied INTO
COPY = re.compile(r"= (\w+)\[([\d,]*)\](\{[^ ]*\})? copy\(")


# what a fused computation may hold beside a `copy` and still be nothing but that relayout
MOVES_ONLY = {"parameter", "copy", "bitcast", "tuple", "get-tuple-element"}
OPCODE = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(")


def copy_ops(text: str):
    """(dtype, dims, layout copied INTO, bytes, alone) of every `copy` in an
    optimized HLO. `alone`: the copy is an instruction of its own or all its
    fusion does, so its bytes are read and written beside everybody else's;
    not alone, it is a fusion's way of reading an operand that lies otherwise
    (PR 48: the Adam update reading a gradient in the matmul's tiling) and
    moves nothing by itself."""
    fused, found, opcodes = False, [], set()
    for line in text.splitlines() + ["}"]:
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            fused, found, opcodes = line.startswith("%fused_computation"), [], set()
        elif line.startswith("}"):
            for dtype, dims, layout in found:
                yield dtype, dims, layout or "", _bytes(dtype, dims), not fused or opcodes <= MOVES_ONLY
            found = []
        else:
            opcode, copy = OPCODE.match(line), COPY.search(line)
            if opcode:
                opcodes.add(opcode.group(1))
            if copy:
                found.append(copy.groups())


def copies(out: str, workloads) -> None:
    """A line a dumped cell: its `copy` ops that stand alone (`copy_ops`) over
    `COPY_OVER` bytes, grouped; those fused into a reader only counted."""
    workloads = workloads or sorted(n[:-len(".hlo.txt")] for n in os.listdir(out) if n.endswith(".hlo.txt"))
    for workload in workloads:
        groups, every, fused = {}, [0, 0], 0
        for dtype, dims, layout, size, alone in copy_ops(open(os.path.join(out, workload + ".hlo.txt")).read()):
            if not alone:
                fused += size > COPY_OVER
                continue
            every[0] += 1
            every[1] += 2 * size
            if size > COPY_OVER:
                group = groups.setdefault("%s[%s]%s" % (dtype, dims, layout), {"copies": 0, "gb": 0.0})
                group["copies"] += 1
                group["gb"] += 2 * size / 1e9
        over = {k: {"copies": v["copies"], "gb": round(v["gb"], 3)} for k, v in sorted(groups.items())}
        line = {"workload": workload,
                "f32_copies_over_4mb": sum(v["copies"] for k, v in over.items() if k.startswith("f32")),
                "f32_gb_over_4mb": round(sum(v["gb"] for k, v in over.items() if k.startswith("f32")), 3),
                "copies_over_4mb": sum(v["copies"] for v in over.values()),
                "gb_over_4mb": round(sum(v["gb"] for v in over.values()), 3),
                "all_copies": every[0], "all_gb": round(every[1] / 1e9, 3),
                "fused_into_readers_over_4mb": fused, "by_shape": over}
        memory = os.path.join(out, workload + ".memory.json")
        if os.path.exists(memory):
            line.update({k + "_gib": round(v / 2**30, 4) for k, v in json.load(open(memory)).items()})
        print(json.dumps(line), flush=True)


def _bytes(dtype: str, dims: str) -> int:
    size = ITEMSIZE[dtype]
    for d in filter(None, dims.split(",")):
        size *= int(d)
    return size


TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)\b")


def kernel(config: "re.Match") -> str:
    """A Mosaic kernel's `backend_config` -> a digest of its MLIR printed
    without source locations (and of what else the config holds)."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    fields, end = json.JSONDecoder().raw_decode(config.group(1))
    body = base64.b64decode(fields["custom_call_config"].pop("body"))
    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True  # the serialized form is a versioned dialect of its own
    with context:
        text = ir.Module.parse(body).operation.get_asm(enable_debug_info=False)
    digest = hashlib.sha1((text + json.dumps(fields, sort_keys=True)).encode()).hexdigest()
    return "backend_config=<kernel %s>%s" % (digest, config.group(1)[end:])


def instructions(path: str):
    """The HLO's lines without what names the source."""
    out, in_table = [], False
    for line in open(path):
        if TABLES.match(line):
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            line = re.sub(r",? ?stack_frame_id=\d+", "", line)
            line = re.sub(r", metadata=\{[^}]*\}", "", line)
            line = re.sub(r'backend_config=(\{[^\n]*"serialization_format"[^\n]*)', kernel, line)
            out.append(line)
    return out


def diff(a: str, b: str) -> int:
    differing = 0
    for name in sorted(n for n in set(os.listdir(a)) | set(os.listdir(b)) if n.endswith(".hlo.txt")):
        paths = [os.path.join(d, name) for d in (a, b)]
        if not all(os.path.exists(p) for p in paths):
            print("%s: on one side only" % name)
            differing += 1
            continue
        left, right = (instructions(p) for p in paths)
        # a checkout's path is in no instruction once the tables are out; lengths first
        pairs = [(x, y) for x, y in zip(left, right) if x != y]
        if len(left) == len(right) and not pairs:
            print("%s: identical, %d lines" % (name, len(left)))
        else:
            differing += 1
            print("%s: DIFFERS (%d against %d lines, %d differing)" % (name, len(left), len(right), len(pairs)))
            for x, y in pairs[:3]:
                print("  < %s\n  > %s" % (x.strip()[:200], y.strip()[:200]))
    return 1 if differing else 0


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "dump":
        dump(sys.argv[2], sys.argv[3], sys.argv[4:])
        sys.exit(0)
    if len(sys.argv) == 4 and sys.argv[1] == "diff":
        sys.exit(diff(sys.argv[2], sys.argv[3]))
    if len(sys.argv) >= 3 and sys.argv[1] == "copies":
        copies(sys.argv[2], sys.argv[3:])
        sys.exit(0)
    print(__doc__, file=sys.stderr)
    sys.exit(2)

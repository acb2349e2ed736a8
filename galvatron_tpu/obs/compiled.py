"""What the compiler made of a request, read off a compiled step's text.

What the trace says (`obs/forms.SCAN_GRADS`, in the `compile` event's
`forms`) is what the program ASKED for; whether
a scanned run's weight gradients are then summed over dp into the shards
ZeRO keeps, or whole onto every chip, is the optimized HLO's to say. The
trainer's `compile` event (cli/train.py) and the tests that hold the compiled
step (tests/ops/test_tpu_compile_steps.py) read it through the same function."""

from __future__ import annotations

import re
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

import numpy as np

Groups = Set[FrozenSet[int]]

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_BYTES))
_IOTA = r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?"
_GROUPS = re.compile(r"replica_groups=(\{\{[\d,{}]*\}\}|%s)" % _IOTA)
_SUM_OPCODE = re.compile(r" (all-reduce|reduce-scatter)(?:-start)?\(")
# a scanned run's backward: the scope `run_layers` gives a run, transposed, inside the scan's loop
SCAN_BACKWARD = re.compile(r'op_name="[^"]*transpose\(jvp\(gt\.layers\.r\d+\)\)/while/body[^"]*"')
LARGE_OPERAND_BYTES = 1 << 20  # under it: the norms' scales, the biases


def replica_groups(line: str) -> Optional[Groups]:
    """The replica groups of an HLO collective, as sets of device positions:
    `{{0,2},{1,3}}`, or the iota form `[2,2]<=[2,2]T(1,0)` (reshape `arange`
    to the dims after `<=`, transpose, reshape to groups x members); None
    where the line names none."""
    found = _GROUPS.search(line)
    if not found:
        return None
    text = found.group(1)
    if text.startswith("{"):
        return {frozenset(int(i) for i in g.split(",")) for g in re.findall(r"\{([\d,]+)\}", text)}
    groups, dims, perm = re.fullmatch(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text).groups()
    ints = lambda t: [int(i) for i in t.split(",")]  # noqa: E731
    ids = np.arange(np.prod(ints(dims))).reshape(ints(dims))
    if perm:
        ids = ids.transpose(ints(perm))
    return {frozenset(row.tolist()) for row in ids.reshape(ints(groups))}


def axis_groups(mesh, axes: Iterable[str]) -> Groups:
    """The groups of device positions (a device's place in `mesh.devices`,
    which the compiled step's `replica_groups` count in) that differ along
    the mesh axes `axes` alone."""
    positions = np.arange(mesh.devices.size).reshape(mesh.devices.shape)
    dims = [mesh.axis_names.index(a) for a in axes]
    members = int(np.prod([positions.shape[d] for d in dims])) if dims else 1
    return {frozenset(row.tolist())
            for row in np.moveaxis(positions, dims, range(-len(dims), 0)).reshape(-1, members)}


def _shape_bytes(text: str) -> List[int]:
    return [_BYTES[dtype] * int(np.prod([int(d) for d in dims.split(",")] if dims else [1]))
            for dtype, dims in _SHAPE.findall(text)]


def _fused_sums(text: str) -> Dict[str, Tuple[List[int], Optional[Groups]]]:
    """name -> (operand bytes, replica groups) of the computations a TPU step
    calls for a sum fused with its slice (`calls=%all-reduce-scatter...`: an
    all-reduce and the dynamic-slice of this chip's shard, one kernel)."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"%(all-reduce-scatter[\w.\-]*) \((.*)\) -> ", line)
        if head:
            name = head.group(1)
            out[name] = (_shape_bytes(head.group(2)), None)
        elif name and line.startswith("}"):
            name = None
        elif name and " all-reduce(" in line:
            out[name] = (out[name][0], replica_groups(line))
    return out


def scan_grad_sums(text: str, dp_groups: Iterable[Groups]) -> List[Tuple[str, int]]:
    """("all-reduce" | "reduce-scatter", operand bytes) of every operand over
    `LARGE_OPERAND_BYTES` that the compiled step `text` sums over one of
    `dp_groups` inside a scanned run's backward body (`SCAN_BACKWARD`): the
    layers' weight gradients. An all-reduce leaves the sum whole on every
    chip of the group; a reduce-scatter, alone or as the TPU compiler writes
    it (a fusion that calls `%all-reduce-scatter`), leaves each chip its
    shard and sends half as much over a pair. A tuple's operands count one
    by one. (The all-reduce INSIDE a fused sum's computation carries no
    `op_name` and is counted with its fusion, once.)"""
    dp_groups = list(dp_groups)
    fused = None
    sums = []
    for line in text.splitlines():
        if not SCAN_BACKWARD.search(line):
            continue
        called = re.search(r"calls=%(all-reduce-scatter[\w.\-]*)", line)
        # `%name = <result shape, or a tuple of them> <opcode>(<operands>), ...`
        summed = _SUM_OPCODE.search(line)
        if called:
            fused = _fused_sums(text) if fused is None else fused
            kind, (sizes, groups) = "reduce-scatter", fused.get(called.group(1), ([], None))
        elif summed:
            kind, groups = summed.group(1), replica_groups(line)
            sizes = _shape_bytes(line[:summed.start()].partition(" = ")[2])
            if kind == "reduce-scatter":  # the result is a member's shard of the operand
                sizes = [n * len(next(iter(groups))) for n in sizes] if groups else sizes
        else:
            continue
        if groups in dp_groups:
            sums += [(kind, n) for n in sizes if n > LARGE_OPERAND_BYTES]
    return sums


def dp_grad_sums_mb(text: str, dp_groups: Iterable[Groups]) -> Dict[str, float]:
    """`scan_grad_sums` in MB (1e6 bytes) a chip and a layer, by kind: the
    `compile` event's `dp_grad_all_reduce_mb` and `dp_grad_reduce_scatter_mb`.
    Operand bytes both: the whole gradient as the chip computed it, before
    the sum."""
    total = {"all-reduce": 0, "reduce-scatter": 0}
    for kind, n in scan_grad_sums(text, dp_groups):
        total[kind] += n
    return {"dp_grad_all_reduce_mb": total["all-reduce"] / 1e6,
            "dp_grad_reduce_scatter_mb": total["reduce-scatter"] / 1e6}

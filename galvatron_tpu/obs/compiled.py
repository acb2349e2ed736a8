"""What the compiler made of a request, read off a compiled step's text.

What the trace says (`obs/forms.SCAN_GRADS`, in the `compile` event's
`forms`) is what the program ASKED for; whether
a scanned run's weight gradients are then summed over dp into the shards
ZeRO keeps, or whole onto every chip, is the optimized HLO's to say. So is
every other collective of the step: which instruction runs it, over which
mesh axes, in which role (a layer's dp, tp, cp are assignments of the mesh's
`pp, m0, m1, ...`: parallel/mesh.py), how many bytes it puts on the wire, and
whether the compiler fused it with its slice or hid it inside a matmul.
`walk` reads the text ONCE; `step_collectives` (the summary's and the
`compile` event's rows) and `scan_grad_sums` (the event's `dp_grad_*_mb`) are
views of that walk. The trainer (cli/train.py) and the tests that hold the
compiled step (tests/ops/test_tpu_compile_steps.py, tests/obs/
test_step_collectives.py) read it through the same functions: this module is
the package's only reader of a compiled step's collectives."""

from __future__ import annotations

import itertools
import re
from typing import Any, Dict, FrozenSet, Iterable, List, NamedTuple, Optional, Sequence, Set, Tuple, Union

import numpy as np

from galvatron_tpu.obs import tracing

Groups = Set[FrozenSet[int]]

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_SHAPE = re.compile(r"\b(%s)\[([\d,]*)\]" % "|".join(_BYTES))
_IOTA = r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?"
_GROUPS = re.compile(r"(?:replica_groups|source_target_pairs)=(\{\{[\d,{}]*\}\}|%s)" % _IOTA)
# a scanned run's backward: the scope `run_layers` gives a run, transposed, inside the scan's loop
_SCAN_BACKWARD = r"transpose\(jvp\(gt\.layers\.r\d+\)\)/while/body"
SCAN_BACKWARD = re.compile(r'op_name="[^"]*%s[^"]*"' % _SCAN_BACKWARD)
LARGE_OPERAND_BYTES = 1 << 20  # under it: the norms' scales, the biases


def replica_groups(line: str) -> Optional[Groups]:
    """The replica groups of an HLO collective, as sets of device positions:
    `{{0,2},{1,3}}`, or the iota form `[2,2]<=[2,2]T(1,0)` (reshape `arange`
    to the dims after `<=`, transpose, reshape to groups x members); a
    collective-permute's `source_target_pairs={{0,2},{2,0}}` as its pairs
    (a pair and its return are one set); None where the line names none."""
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


# ------------------------------------------------------------------ the walk
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")
_MATMULS = ("convolution", "dot")
_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_NAMES = re.compile(r"%([\w.\-]+)")
# the computations an instruction hands control to: their instructions run as
# instructions of the step, and the device trace names them
_CONTROL = re.compile(r"\b(?:body|condition|true_computation|false_computation)=%([\w.\-]+)"
                      r"|\bbranch_computations=\{([^}]*)\}")
_CALLED = re.compile(r"\b(?:calls|to_apply|called_computations)=\{?%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CHANNEL = re.compile(r"channel_id=(\d+)")
_ASYNC = re.compile(r'custom_call_target="AsyncCollective(Start|Done)"')


class _Instruction(NamedTuple):
    name: str
    opcode: str
    shape: str  # the result's, a tuple's elements in one string
    operands: List[str]
    root: bool
    line: str


class _Held(NamedTuple):
    """What a computation holds, with all it calls."""
    collectives: List[Dict[str, Any]]
    matmul: bool
    mark: Optional[str]  # "start" | "done": the TPU compiler's AsyncCollectiveStart / AsyncCollectiveDone
    origin: Tuple[int, str]  # the op_name a fusion that carries none is known by: a matmul's (2), the root's (1)


def _collective(opcode: str) -> Tuple[Optional[str], str]:
    """(kind, form) of a collective opcode; (None, "") of any other
    (`copy-start` and `slice-start` end so too and are none)."""
    for suffix, form in (("-start", "start"), ("-done", "done"), ("", "plain")):
        kind = opcode[:len(opcode) - len(suffix)]
        if opcode.endswith(suffix) and kind in COLLECTIVES:
            return kind, form
    return None, ""


def _computations(text: str) -> Tuple[Dict[str, Dict[str, _Instruction]], Optional[str]]:
    """computation -> name -> instruction in the text's order, and the entry
    computation's name. A line that starts no instruction (the rest of a
    `pallas_call` printed over several lines) belongs to none."""
    out: Dict[str, Dict[str, _Instruction]] = {}
    entry = inside = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = out.setdefault(head.group(2), {})
            entry = head.group(2) if head.group(1) else entry
            continue
        found = _INSTRUCTION.match(line) if inside is not None else None
        opcode = _OPCODE.search(found.group(3)) if found else None
        if opcode:
            rest = found.group(3)
            inside[found.group(2)] = _Instruction(
                found.group(2), opcode.group(1), rest[:opcode.start()],
                _NAMES.findall(rest[opcode.end():].split(")", 1)[0]), bool(found.group(1)), line)
    return out, entry


def _operand_bytes(inside: Dict[str, _Instruction], ins: _Instruction) -> List[int]:
    """This chip's operand bytes, one an operand (a tuple's summed): the
    text prints an operand by name, and its shape where it is defined."""
    return [sum(_shape_bytes(inside[name].shape)) for name in ins.operands if name in inside]


def _record(inside: Dict[str, _Instruction], ins: _Instruction, kind: str, form: str) -> Dict[str, Any]:
    channel = _CHANNEL.search(ins.line)
    return dict(instruction=ins.name, kind=kind, form=form, groups=replica_groups(ins.line),
                operands=_operand_bytes(inside, ins), channel=channel.group(1) if channel else None)


def walk(text: Union[str, List[Dict[str, Any]]]) -> List[Dict[str, Any]]:
    """One walk of a compiled step's text: a record for each instruction THE
    DEVICE TRACE NAMES (one of the entry computation, or of a computation
    control passes to: a `while`'s body and condition, a conditional's
    branches, a `call`'s target; never one inside a fused computation) that
    runs a collective, found by opcode and by what the computations it calls
    hold, never by its name (a `shard_map`'s `all_to_all.3` and
    `psum_invariant.7` are an all-to-all and an all-reduce):

    - a collective opcode itself: `plain`, or its `-start` / `-done`;
    - an instruction whose called computations hold a collective, at any
      depth: `start` / `done` where they end in the TPU compiler's
      `AsyncCollectiveStart` / `AsyncCollectiveDone` (the fusions the trace
      calls `async-collective-start.N`) or the instruction is an
      `async-start` / `async-done`; `hidden` where they hold a matmul too
      (`convolution`, `dot`; `%async_collective_fusion.N`: the collective
      rides a matmul and the fusion's time is the matmul's); `fused`
      otherwise (the sum and the slice of this chip's shard in one kernel,
      `%all-reduce-scatter.N`, and whatever else the walk finds).

    A record: `instruction`, `kind`, `form`, `groups` (`replica_groups`),
    `operands` (this chip's operand bytes, one an operand), `op_name` (its
    own, else its called computations': a matmul's, else the root's, as the
    benchmark's `trace.origins_from_hlo` labels a fusion) and `channel`. One
    collective the compiler spread over several instructions (a start, the
    matmuls that carry it, a done: one `channel_id`) keeps its bytes ONCE, on
    its first `hidden` record if it has one, else on its `start`; the others'
    `operands` are empty, as a `-done`'s always are. A fused all-reduce whose
    result is smaller than its operand is a `reduce-scatter`. What was walked
    already (a list) comes back as it is."""
    if not isinstance(text, str):
        return text
    computations, entry = _computations(text)
    memo: Dict[str, _Held] = {}

    def called_by(ins: _Instruction) -> _Held:
        parts = [held(name) for name in _CALLED.findall(ins.line)]
        marks = [p.mark for p in parts if p.mark]
        return _Held([c for p in parts for c in p.collectives], any(p.matmul for p in parts),
                     marks[0] if marks else None, max([p.origin for p in parts], default=(-1, "")))

    def held(name: str) -> _Held:
        if name not in memo:
            memo[name] = _Held([], False, None, (-1, ""))  # (a cycle finds this)
            inside = computations.get(name, {})
            found, matmul, mark, origin = [], False, None, (-1, "")
            for ins in inside.values():
                kind, form = _collective(ins.opcode)
                if kind and form != "done":
                    found.append(_record(inside, ins, kind, form))
                below = called_by(ins)
                async_mark = _ASYNC.search(ins.line)
                found += below.collectives
                matmul = matmul or below.matmul or ins.opcode in _MATMULS
                mark = mark or below.mark or (async_mark.group(1).lower() if async_mark else None)
                op_name = _OP_NAME.search(ins.line)
                rank = 2 if ins.opcode in _MATMULS else 1 if ins.root else 0
                if op_name and rank >= origin[0]:
                    origin = (rank, op_name.group(1))
                origin = max(origin, below.origin, key=lambda o: o[0])
            memo[name] = _Held(found, matmul, mark, origin)
        return memo[name]

    records: List[Dict[str, Any]] = []
    traced, seen = [entry] if entry else [], set()
    while traced:
        name = traced.pop()
        if name in seen:
            continue
        seen.add(name)
        inside = computations.get(name, {})
        here: Dict[str, Dict[str, Any]] = {}
        for ins in inside.values():
            for one, several in _CONTROL.findall(ins.line):
                traced += [one] if one else _NAMES.findall(several)
            if ins.opcode == "call":
                traced += _CALLED.findall(ins.line)
                continue
            op_name = _OP_NAME.search(ins.line)
            op_name = op_name.group(1) if op_name else ""
            kind, form = _collective(ins.opcode)
            if form == "done":  # names its start, and says nothing else
                started = here.get(ins.operands[0] if ins.operands else "", {})
                record = dict(started, instruction=ins.name, kind=kind, form="done", operands=[], channel=None)
                op_name = op_name or started.get("op_name", "")
            elif kind:
                record = _record(inside, ins, kind, form)
            else:
                below = called_by(ins)
                if not below.collectives:
                    continue
                largest = max(below.collectives, key=lambda c: sum(c["operands"]))
                suffix = ins.opcode.rpartition("-")[2]
                form = below.mark or (suffix if suffix in ("start", "done") else "hidden" if below.matmul else "fused")
                kind = largest["kind"]
                if form == "fused" and kind == "all-reduce" and sum(_shape_bytes(ins.shape)) < sum(largest["operands"]):
                    kind = "reduce-scatter"
                record = dict(largest, instruction=ins.name, kind=kind, form=form,
                              operands=[] if form == "done" else largest["operands"])
                op_name = op_name or below.origin[1]
            record["op_name"] = op_name
            here[ins.name] = record
            records.append(record)
    # one collective over a start, the matmuls that carry it and a done: its bytes stand on the first of the
    # matmuls (a `plain` or `fused` record is a whole collective, and a `shard_map`'s share one channel_id)
    carrier: Dict[str, Dict[str, Any]] = {}
    for record in records:
        if record["form"] == "hidden" and record["channel"]:
            carrier.setdefault(record["channel"], record)
    for record in records:
        if record["form"] in ("hidden", "start") and carrier.get(record["channel"], record) is not record:
            record["operands"] = []
    return records


# ------------------------------------------------------------------ the rows
_SCOPE = re.compile(r"gt(?:\.[a-z][a-z_0-9]*)+")
_RUN = re.compile(re.escape(tracing.LAYERS).replace("%d", r"(\d+)"))
_VOCAB = re.compile("(?:%s)(?![a-z_.])" % "|".join(re.escape(s) for s in (tracing.EMBED, tracing.HEAD_LOSS, tracing.MTP)))
_ROLES = ("dp", "tp", "cp")
# what a chip sends, in operands, by the ring's count over a group of g
_WIRE = {"all-reduce": lambda g: 2 * (g - 1) / g, "reduce-scatter": lambda g: (g - 1) / g,
         "all-to-all": lambda g: (g - 1) / g, "all-gather": lambda g: g - 1, "collective-permute": lambda g: 1}


def _phase(op_name: str) -> Optional[str]:
    if "rematted_computation" in op_name:
        return "remat"
    return "bwd" if "transpose(" in op_name else "fwd" if "jvp(" in op_name else None


def _axes_of(mesh) -> Dict[FrozenSet[FrozenSet[int]], Tuple[str, ...]]:
    """groups -> the mesh axes (of more than one device) they run over."""
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    return {frozenset(axis_groups(mesh, axes)): axes
            for n in range(len(wide), 0, -1) for axes in itertools.combinations(wide, n)}


def _pair_axes(mesh, pairs: Groups) -> Tuple[str, ...]:
    """The mesh axes along which a permute's pairs differ."""
    differ = set()
    for pair in pairs:
        where = np.array(np.unravel_index(sorted(pair), mesh.devices.shape))
        differ |= {mesh.axis_names[d] for d in np.nonzero(where.min(axis=1) != where.max(axis=1))[0]}
    return tuple(a for a in mesh.axis_names if a in differ)


def _role(axes: Sequence[str], layouts) -> str:
    """The fields of `LayerAxes` that `axes` fill, for layouts that agree."""
    from galvatron_tpu.parallel.mesh import PP_AXIS

    said = set()
    for layout in layouts:
        fields = [(role, tuple(getattr(layout, role))) for role in _ROLES]
        if not any(PP_AXIS in field for _, field in fields):
            fields.append(("pp", (PP_AXIS,)))
        filled = [(role, field) for role, field in fields if field and set(field) <= set(axes)]
        whole = {a for _, field in filled for a in field} == set(axes)
        said.add("+".join(role for role, _ in filled) if filled and whole else "other")
    return said.pop() if len(said) == 1 else "other"


def step_collectives(text, mesh, hp, kinds: Optional[Sequence[str]] = None) -> List[Dict[str, Any]]:
    """Every collective of the compiled step `text` (or of its `walk`), a row
    an instruction the device trace names, for the mesh and the layout `hp`
    it was compiled under (`kinds`: `config/strategy.model_layer_kinds` of
    the model, where its runs split on kind). A row:

    - `instruction`: as the trace prints it (`fusion.502`, `all-gather.384`,
      `collective-permute-done.3`): what a reader joins it to an op by;
    - `kind`: all-reduce, reduce-scatter, all-gather, all-to-all,
      collective-permute; `form`: plain, start, done, fused, hidden (`walk`);
    - `group`: the members of a replica group (2 for a permute's pair);
      `axes`: the mesh axes those groups run over (of the axes with more
      than one device; for a permute the axes along which its pairs differ;
      [] where no set of axes gives these groups);
    - `role`: what those axes ARE for the layers the row's scope selects:
      the fields of `parallel/mesh.LayerAxes` they fill, `dp`, `tp`, `cp`
      (a Ulysses all-to-all runs over the tp axes: `tp`) or `pp` (the pp
      axis where no field holds it), a union joined by `+` in that order
      (`dp+tp`: a scalar sum over all of a stage's chips), `other` where
      they fill none exactly or the selected layers disagree. A
      `gt.layers.r<k>` anywhere in the op_name selects the layers of run k
      of `layer_runs(hp, kinds)`; else `gt.embed`, `gt.head_loss`, `gt.mtp`
      select the vocabulary's layout, `pipeline_vocab_axes` (whose tp spans
      pp in the scan pipeline) and, where that says `other`, `vocab_axes`;
      any other scope (a part's with no run around it in the pipeline's
      tick body; `gt.param_gather`, `gt.optimizer`, `gt.guard`, whose state
      is every layer's: dp wherever the layouts agree) and NO scope select
      every layer and the vocabulary, the vocabulary in the layers' terms
      (`vocab_axes` first, within a stage, so a sum over all of a pipeline's
      chips is `tp+pp` to both; `pipeline_vocab_axes` where that says `other`);
    - `operand_bytes`: this chip's operand, a tuple's summed; `wire_bytes`:
      what the chip sends by a ring's count over a group of g: all-reduce
      2 (g - 1) / g x the operand, reduce-scatter and all-to-all (g - 1) / g,
      all-gather (g - 1), permute 1. A collective's bytes stand on ONE row
      (`walk`): a `done` has 0, and a `start` whose matmuls carry it;
    - `scope`: the innermost `gt.` scope of its op_name; `phase`: fwd,
      remat, bwd by the transform's wrapper, None outside them."""
    from galvatron_tpu.config.strategy import layer_runs
    from galvatron_tpu.parallel.mesh import layer_axes, pipeline_vocab_axes, vocab_axes

    axes_of = _axes_of(mesh)
    runs = layer_runs(hp, kinds)
    layers = [layer_axes(hp, i) for i in range(len(hp.layers))]
    # (equal layouts answer alike: one of each is asked)
    everywhere = list(dict.fromkeys(layers))

    def vocab_role(axes, layouts):
        role = _role(axes, [layouts[0](hp)])
        return _role(axes, [layouts[1](hp)]) if role == "other" else role

    def role_of(axes, op_name):
        run = _RUN.search(op_name)
        if run and int(run.group(1)) < len(runs):
            return _role(axes, dict.fromkeys(layers[i] for i in runs[int(run.group(1))].layer_indices))
        if _VOCAB.search(op_name):
            return vocab_role(axes, (pipeline_vocab_axes, vocab_axes))
        said = {_role(axes, [layout]) for layout in everywhere} | {vocab_role(axes, (vocab_axes, pipeline_vocab_axes))}
        return said.pop() if len(said) == 1 else "other"

    rows = []
    for record in walk(text):
        groups, kind, op_name = record.get("groups") or set(), record["kind"], record["op_name"]
        permute = kind == "collective-permute"
        axes = _pair_axes(mesh, groups) if permute else axes_of.get(frozenset(groups), ())
        group = 2 if permute else len(next(iter(groups), ()))
        operand = sum(record["operands"])
        scopes = _SCOPE.findall(op_name)
        rows.append({
            "instruction": record["instruction"], "kind": kind, "form": record["form"], "group": group,
            "axes": list(axes), "role": role_of(axes, op_name), "operand_bytes": operand,
            "wire_bytes": _WIRE[kind](group) * operand if group else 0.0,
            "scope": scopes[-1] if scopes else None, "phase": _phase(op_name)})
    return rows


def scan_grad_sums(text, dp_groups: Iterable[Groups]) -> List[Tuple[str, int]]:
    """("all-reduce" | "reduce-scatter", operand bytes) of every operand over
    `LARGE_OPERAND_BYTES` that the compiled step `text` (or its `walk`) sums
    over one of `dp_groups` inside a scanned run's backward body
    (`SCAN_BACKWARD`): the layers' weight gradients. An all-reduce leaves the
    sum whole on every chip of the group; a reduce-scatter, alone or as the
    TPU compiler writes it (a fusion that calls `%all-reduce-scatter`),
    leaves each chip its shard and sends half as much over a pair. A tuple's
    operands count one by one."""
    dp_groups = list(dp_groups)
    return [(record["kind"], n) for record in walk(text)
            if record["kind"] in ("all-reduce", "reduce-scatter") and record.get("groups") in dp_groups
            and re.search(_SCAN_BACKWARD, record["op_name"])
            for n in record["operands"] if n > LARGE_OPERAND_BYTES]


def dp_grad_sums_mb(text, dp_groups: Iterable[Groups]) -> Dict[str, float]:
    """`scan_grad_sums` in MB (1e6 bytes) a chip and a layer, by kind: the
    `compile` event's `dp_grad_all_reduce_mb` and `dp_grad_reduce_scatter_mb`.
    Operand bytes both: the whole gradient as the chip computed it, before
    the sum."""
    total = {"all-reduce": 0, "reduce-scatter": 0}
    for kind, n in scan_grad_sums(text, dp_groups):
        total[kind] += n
    return {"dp_grad_all_reduce_mb": total["all-reduce"] / 1e6,
            "dp_grad_reduce_scatter_mb": total["reduce-scatter"] / 1e6}

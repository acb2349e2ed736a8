"""`obs/compiled.step_collectives`: every collective of a compiled step, a
row an instruction the device trace names (PR 68). On a hand-written text that
holds each printed form; on the two four-chip benchmark cells and on
tests/ops/test_scan_grad_sums.py's five layouts compiled for a DESCRIBED v5e
2x2 (no chip attached: tests/ops/tpu_compile.py); and on the CPU's four-device
mesh through `cli train` and `cli report`."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.obs import compiled as C
from galvatron_tpu.obs import report as R
from galvatron_tpu.obs import telemetry as T
from galvatron_tpu.parallel.mesh import layer_axes
from tests.ops.test_scan_grad_sums import CASES
from tests.ops.test_tpu_compile_steps import _cell_model_and_step
from tests.ops.tpu_compile import _model_and_compiled_step, v5e_2x2  # noqa: F401  (the fixture)

FIELDS = {"instruction", "kind", "form", "group", "axes", "role", "operand_bytes", "wire_bytes", "scope", "phase"}

# A step as the TPU compiler prints one, cut to what the walk reads: device
# positions 0..3 on a (pp 1, m0 2, m1 2) mesh, so {0,2},{1,3} run over m0 (dp
# of a tp 2 x dp 2 layout), {0,1},{2,3} over m1 (tp) and [1,4]<=[4] over both.
HANDWRITTEN = '''HloModule jit_plain_step

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%all-reduce-scatter.3 (input.3: bf16[8,512]) -> bf16[4,512] {
  %input.3 = bf16[8,512]{1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.30 = bf16[8,512]{1,0} all-reduce(%input.3), channel_id=7, replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%region_0.1
  %c = u32[] constant(0)
  ROOT %dynamic-slice.3 = bf16[4,512]{1,0} dynamic-slice(%all-reduce.30, %c, %c), dynamic_slice_sizes={4,512}
}

%fused_computation.40 (p0: bf16[16,64]) -> (bf16[16,64], bf16[16,128], u32[]) {
  %p0 = bf16[16,64]{1,0} parameter(0)
  %all-gather.41 = bf16[16,128]{1,0} all-gather(%p0), channel_id=9, replica_groups=[2,2]<=[4], dimensions={1}, metadata={op_name="jit(plain_step)/jvp(gt.layers.r0)/while/body/gt.mlp/dot_general"}
  ROOT %custom-call.4 = (bf16[16,64]{1,0}, bf16[16,128]{1,0}, u32[]) custom-call(%all-gather.41), custom_call_target="AsyncCollectiveStart"
}

%async_collective_fusion.42 (p0.1: bf16[16,64], p1.1: bf16[128,32]) -> bf16[16,32] {
  %p0.1 = bf16[16,64]{1,0} parameter(0)
  %p1.1 = bf16[128,32]{1,0} parameter(1)
  %all-gather.43 = bf16[16,128]{1,0} all-gather(%p0.1), channel_id=9, replica_groups=[2,2]<=[4], dimensions={1}
  ROOT %convolution.4 = bf16[16,32]{1,0} convolution(%all-gather.43, %p1.1), dim_labels=bf_io->bf, metadata={op_name="jit(plain_step)/jvp(gt.layers.r0)/while/body/gt.mlp/dot_general"}
}

%fused_computation.44 (p0.2: bf16[16,64], p1.2: bf16[16,128]) -> bf16[16,128] {
  %p0.2 = bf16[16,64]{1,0} parameter(0)
  %p1.2 = bf16[16,128]{1,0} parameter(1)
  %all-gather.45 = bf16[16,128]{1,0} all-gather(%p0.2), channel_id=9, replica_groups=[2,2]<=[4], dimensions={1}
  ROOT %custom-call.5 = bf16[16,128]{1,0} custom-call(%p0.2, %p1.2, %all-gather.45), custom_call_target="AsyncCollectiveDone"
}

%body.5 (carry: (bf16[16,64], bf16[128,32], bf16[8,512])) -> (bf16[16,64], bf16[128,32], bf16[8,512]) {
  %carry = (bf16[16,64]{1,0}, bf16[128,32]{1,0}, bf16[8,512]{1,0}) parameter(0)
  %x = bf16[16,64]{1,0} get-tuple-element(%carry), index=0
  %w = bf16[128,32]{1,0} get-tuple-element(%carry), index=1
  %g = bf16[8,512]{1,0} get-tuple-element(%carry), index=2
  %async-collective-start = (bf16[16,64]{1,0}, bf16[16,128]{1,0}, u32[]) fusion(%x), kind=kCustom, calls=%fused_computation.40
  %fusion.42 = bf16[16,32]{1,0} fusion(%x, %w), kind=kOutput, calls=%async_collective_fusion.42, metadata={op_name="jit(plain_step)/jvp(gt.layers.r0)/while/body/gt.mlp/dot_general"}
  %async-collective-done = bf16[16,128]{1,0} fusion(%x, %x), kind=kCustom, calls=%fused_computation.44, metadata={op_name="jit(plain_step)/jvp(gt.layers.r0)/while/body/gt.mlp/dot_general"}
  %fusion.3 = bf16[4,512]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.3, metadata={op_name="jit(plain_step)/transpose(jvp(gt.layers.r0))/while/body/closed_call/checkpoint/rematted_computation/gt.attn.proj/dot_general"}
  %all_to_all.6 = bf16[16,64]{1,0} all-to-all(%x), channel_id=1, replica_groups={{0,1},{2,3}}, dimensions={0}, metadata={op_name="jit(plain_step)/transpose(jvp(gt.layers.r0))/while/body/shard_map/all_to_all"}
  ROOT %tuple.5 = (bf16[16,64]{1,0}, bf16[128,32]{1,0}, bf16[8,512]{1,0}) tuple(%all_to_all.6, %w, %g)
}

%cond.5 (carry.1: (bf16[16,64], bf16[128,32], bf16[8,512])) -> pred[] {
  %carry.1 = (bf16[16,64]{1,0}, bf16[128,32]{1,0}, bf16[8,512]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main.9_spmd (param.0: (bf16[16,64], bf16[128,32], bf16[8,512]), param.1: f32[256], param.2: f32[3]) -> f32[3] {
  %param.0 = (bf16[16,64]{1,0}, bf16[128,32]{1,0}, bf16[8,512]{1,0}) parameter(0)
  %param.1 = f32[256]{0} parameter(1)
  %param.2 = f32[3]{0} parameter(2)
  %while.5 = (bf16[16,64]{1,0}, bf16[128,32]{1,0}, bf16[8,512]{1,0}) while(%param.0), condition=%cond.5, body=%body.5
  %all-gather-start.7 = (f32[256]{0}, f32[512]{0}) all-gather-start(%param.1), channel_id=3, replica_groups={{0,2},{1,3}}, dimensions={0}, metadata={op_name="jit(plain_step)/gt.param_gather/convert_element_type"}
  %all-gather-done.7 = f32[512]{0} all-gather-done(%all-gather-start.7), metadata={op_name="jit(plain_step)/gt.param_gather/convert_element_type"}
  %collective-permute-start.8 = (f32[256]{0}, f32[256]{0}, u32[], u32[]) collective-permute-start(%param.1), channel_id=4, source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
  %collective-permute-done.8 = f32[256]{0} collective-permute-done(%collective-permute-start.8)
  %copy-start.1 = (f32[256]{0}, f32[256]{0}, u32[]) copy-start(%param.1)
  %psum.9 = (f32[3]{0}, f32[256]{0}) all-reduce(%param.2, %param.1), channel_id=1, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%region_0.1, metadata={op_name="jit(plain_step)/gt.optimizer/reduce_sum"}
  ROOT %loss.10 = f32[3]{0} all-reduce(%param.2), channel_id=5, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, to_apply=%region_0.1, metadata={op_name="jit(plain_step)/jvp(gt.head_loss)/reduce_sum"}
}
'''


@pytest.fixture(scope="module")
def tp2dp2_mesh(devices8):
    hp = HybridParallelConfig.uniform(4, 2, tp=2, vocab_tp=2, default_dp_type="zero2", global_bsz=4)
    return Mesh(np.array(devices8[:4]).reshape(1, 2, 2), ("pp", "m0", "m1")), hp


def test_each_printed_form_on_a_handwritten_step(tp2dp2_mesh):
    mesh, hp = tp2dp2_mesh
    rows = {row["instruction"]: row for row in C.step_collectives(HANDWRITTEN, mesh, hp)}
    assert list(rows) and all(set(row) == FIELDS for row in rows.values())

    def said(name, *keys):
        return tuple(rows[name][k] for k in keys)

    # every instruction the trace would name, the loop's body's among them, and none of a fused computation
    assert sorted(rows) == sorted([
        "async-collective-start", "fusion.42", "async-collective-done", "fusion.3", "all_to_all.6",
        "all-gather-start.7", "all-gather-done.7", "collective-permute-start.8", "collective-permute-done.8",
        "psum.9", "loss.10"])
    # the TPU compiler's async collective: a start, the matmul that carries it, a done; its bytes on the matmul
    kind_form = ("kind", "form", "axes", "role", "operand_bytes")
    assert said("async-collective-start", *kind_form) == ("all-gather", "start", ["m1"], "tp", 0)
    assert said("fusion.42", *kind_form) == ("all-gather", "hidden", ["m1"], "tp", 16 * 64 * 2)
    assert said("async-collective-done", *kind_form) == ("all-gather", "done", ["m1"], "tp", 0)
    assert said("fusion.42", "wire_bytes", "scope", "phase") == (16 * 64 * 2, "gt.mlp", "fwd")
    assert said("async-collective-start", "scope") == ("gt.mlp",)  # its computation's, having none of its own
    # a sum fused with the slice of this chip's shard: a reduce-scatter over dp, braces
    assert said("fusion.3", "kind", "form", "group", "axes", "role") == ("reduce-scatter", "fused", 2, ["m0"], "dp")
    assert said("fusion.3", "operand_bytes", "wire_bytes", "scope", "phase") == (8192, 4096.0, "gt.attn.proj", "remat")
    # a shard_map's collective is one by its opcode, whatever its name
    assert said("all_to_all.6", "kind", "form", "role", "wire_bytes", "scope", "phase") == (
        "all-to-all", "plain", "tp", 1024.0, "gt.layers.r0", "bwd")
    # a -start / -done pair of the opcode's own: the bytes on the start, the done says what its start says
    assert said("all-gather-start.7", "form", "axes", "role", "operand_bytes", "wire_bytes", "scope", "phase") == (
        "start", ["m0"], "dp", 1024, 1024, "gt.param_gather", None)
    assert said("all-gather-done.7", "kind", "form", "axes", "role", "operand_bytes", "wire_bytes") == (
        "all-gather", "done", ["m0"], "dp", 0, 0)
    # source_target_pairs: the axes the pairs differ along, a pair is a group of 2, a permute sends its operand once
    assert said("collective-permute-start.8", "kind", "group", "axes", "role", "wire_bytes", "scope") == (
        "collective-permute", 2, ["m1"], "tp", 1024, None)
    assert said("collective-permute-done.8", "form", "axes", "role", "wire_bytes") == ("done", ["m1"], "tp", 0)
    # iota groups over all four; a tuple's operands summed; a union of two roles in the order dp, tp
    assert said("psum.9", "kind", "group", "axes", "role", "operand_bytes", "wire_bytes", "scope") == (
        "all-reduce", 4, ["m0", "m1"], "dp+tp", 12 + 1024, 2 * 3 / 4 * 1036, "gt.optimizer")
    # the transposed iota form [2,2]<=[2,2]T(1,0) is {0,2},{1,3}: dp, under the vocabulary's scope
    assert said("loss.10", "group", "axes", "role", "scope", "phase") == (2, ["m0"], "dp", "gt.head_loss", "fwd")
    # `psum.9` and `all_to_all.6` share channel_id=1 (a shard_map's do) and are two collectives: both keep their bytes
    assert rows["all_to_all.6"]["operand_bytes"] == 2048
    # what was walked is not walked again, and the sums over dp are a view of the same walk
    walked = C.walk(HANDWRITTEN)
    assert C.walk(walked) is walked and len(walked) == len(rows)
    dp = [C.axis_groups(mesh, ("m0",))]
    assert C.scan_grad_sums(walked, dp) == []  # 8 KB: under LARGE_OPERAND_BYTES
    assert C.replica_groups("source_target_pairs={{0,2},{2,0}}") == {frozenset({0, 2})}


def test_a_layout_whose_layers_disagree_says_other(devices8):
    """Layer 0 tp 2 x dp 2, layer 1 dp 4: a sum over m1 under layer 0's run is
    its tp, under layer 1's part of its dp (no field exactly: `other`), and
    with no scope the layers disagree; groups no set of mesh axes gives have
    no axes."""
    from galvatron_tpu.config.strategy import LayerStrategy

    mesh = Mesh(np.array(devices8[:4]).reshape(1, 2, 2), ("pp", "m0", "m1"))
    hp = HybridParallelConfig(world_size=4, pp=1, layers=[LayerStrategy(tp=2), LayerStrategy(tp=1)],
                              global_bsz=4, vocab_tp=1)
    assert (layer_axes(hp, 0).tp, layer_axes(hp, 1).dp) == (("m1",), ("m0", "m1"))
    line = ('  %%all-reduce.%d = f32[4]{0} all-reduce(%%p), channel_id=%d, replica_groups=%s, '
            'to_apply=%%add, metadata={op_name="jit(step)/%s/reduce_sum"}')
    text = "\n".join([
        "ENTRY %main (p: f32[4]) -> f32[4] {", "  %p = f32[4]{0} parameter(0)",
        line % (1, 1, "{{0,1},{2,3}}", "jvp(gt.layers.r0)"), line % (2, 2, "{{0,1},{2,3}}", "jvp(gt.layers.r1)"),
        line % (3, 3, "{{0,1},{2,3}}", "gt.optimizer"), line % (4, 4, "{{0,1,2,3}}", "jvp(gt.layers.r1)"),
        line % (5, 5, "{{0,3},{1,2}}", "jvp(gt.layers.r0)"), "}"])
    rows = C.step_collectives(text, mesh, hp)
    assert [(r["axes"], r["role"]) for r in rows] == [
        (["m1"], "tp"), (["m1"], "other"), (["m1"], "other"), (["m0", "m1"], "dp"), ([], "other")]


# ------------------------------------------------- compiled for a described v5e 2x2
def instructions_that_hold_a_collective(text):
    """The names of the instructions outside fused computations whose opcode
    is a collective's or whose called computation holds one at any depth:
    written apart from `obs/compiled.walk`, with regular expressions over
    the text's blocks."""
    opcode = r" (?:%s)(?:-start|-done)?\(" % "|".join(C.COLLECTIVES)
    blocks = dict(re.findall(r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text, re.S | re.M))
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))

    def holds(name, seen=()):
        body = blocks.get(name, "")
        return bool(re.search(opcode, body)) or any(
            holds(n, seen + (name,)) for n in re.findall(r"calls=%([\w.\-]+)", body) if n not in seen)

    names = []
    for name, body in blocks.items():
        if name in fused:
            continue
        for line in body.splitlines():
            called = re.search(r"calls=%([\w.\-]+)", line)
            if re.search(opcode, line) or (called and holds(called.group(1))):
                names.append(re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1))
    return names


@pytest.fixture(scope="module")
def cell_rows(v5e_2x2):  # noqa: F811
    """workload -> (model, the compiled step's text, its rows), compiled once
    a cell at its own size (about a minute and half a minute)."""
    out = {}

    def rows_of(workload):
        if workload not in out:
            model, step = _cell_model_and_step(workload, v5e_2x2)
            text = step.as_text()
            out[workload] = model, text, C.step_collectives(text, model.mesh, model.hp)
        return out[workload]

    return rows_of


@pytest.mark.parametrize("workload", ["qwen7-c4-tp2dp2", "qwen7-c4-pp2tp2"])
def test_every_collective_of_a_four_chip_cells_step_is_one_row(cell_rows, workload):
    model, text, rows = cell_rows(workload)
    assert sorted(r["instruction"] for r in rows) == sorted(instructions_that_hold_a_collective(text))
    assert len({r["instruction"] for r in rows}) == len(rows) > 100
    assert all(set(r) == FIELDS for r in rows)
    # the hidden rows are the fusions that call `%async_collective_fusion*` and no others
    carried = re.findall(r"^\s*%([\w.\-]+) = [^\n]*calls=%async_collective_fusion", text, re.M)
    assert sorted(r["instruction"] for r in rows if r["form"] == "hidden") == sorted(carried) and carried
    # a collective the compiler spread over a start, its matmuls and a done sends its bytes once
    starts = [r for r in rows if r["form"] == "start" and r["kind"] != "collective-permute"]
    assert starts and all(r["wire_bytes"] == 0 for r in starts)
    assert all(r["wire_bytes"] == 0 for r in rows if r["form"] == "done")
    assert all(r["wire_bytes"] > 0 for r in rows if r["form"] in ("plain", "fused"))
    # no row without axes, none whose axes fill no role
    assert not [r for r in rows if not r["axes"] or r["role"] == "other"]


def test_the_dp_cells_rows_run_over_dp_tp_or_both(cell_rows):
    """`qwen7-c4-tp2dp2`: replica groups {0,1},{2,3} (m1: tp), {0,2},{1,3} (m0:
    dp) and all four (the scalar sums of the loss and of the gradient norm, and
    one sum under `gt.layers.r0`); the layers' gradients leave through fused
    reduce-scatters over dp; ZeRO-2's copy is gathered over dp under
    `gt.param_gather`; and the `compile` event's two sums are a view of the
    same walk."""
    model, text, rows = cell_rows("qwen7-c4-tp2dp2")
    assert {(tuple(r["axes"]), r["role"]) for r in rows} == {(("m0",), "dp"), (("m1",), "tp"), (("m0", "m1"), "dp+tp")}
    forms = collections.Counter(r["form"] for r in rows)
    assert forms["fused"] >= 10 and forms["hidden"] >= 4 and forms["start"] == forms["done"] >= 4
    grads = [r for r in rows if (r["role"], r["kind"], r["form"], r["phase"]) == ("dp", "reduce-scatter", "fused", "bwd")
             and r["scope"] in ("gt.attn.proj", "gt.mlp")]
    assert sum(r["operand_bytes"] for r in grads) / 1e6 == pytest.approx(233.0, abs=0.05)
    assert all(r["wire_bytes"] == r["operand_bytes"] / 2 for r in grads)
    gathered = [r for r in rows if r["scope"] == "gt.param_gather"]
    assert gathered and {(r["kind"], r["role"]) for r in gathered} == {("all-gather", "dp")}
    dp = [C.axis_groups(model.mesh, layer_axes(model.hp, 0).dp)]
    assert C.dp_grad_sums_mb(C.walk(text), dp) == C.dp_grad_sums_mb(text, dp) == {
        "dp_grad_all_reduce_mb": 0.0, "dp_grad_reduce_scatter_mb": pytest.approx(233.0, abs=0.05)}


def test_the_pipelined_cells_sends_are_pp_and_its_vocabulary_spans_pp_and_tp(cell_rows):
    """`qwen7-c4-pp2tp2`: six `collective-permute` pairs of role `pp`; the
    vocabulary's sums over ('pp',) + tp are its tp (`pipeline_vocab_axes`); the
    optimizer's scalar sum over all four chips is `tp+pp` to the layers and
    the vocabulary alike; nothing runs over a dp axis."""
    model, text, rows = cell_rows("qwen7-c4-pp2tp2")
    permutes = [r for r in rows if r["kind"] == "collective-permute"]
    assert collections.Counter((r["form"], r["role"], tuple(r["axes"]), r["group"]) for r in permutes) == {
        ("start", "pp", ("pp",), 2): 6, ("done", "pp", ("pp",), 2): 6}
    assert all(r["wire_bytes"] == r["operand_bytes"] > 0 for r in permutes if r["form"] == "start")
    vocabulary = [r for r in rows if r["scope"] in ("gt.embed", "gt.head_loss") and r["axes"] == ["pp", "m0"]]
    assert vocabulary and {r["role"] for r in vocabulary} == {"tp"}
    assert {r["role"] for r in rows} == {"tp", "pp", "tp+pp"}
    assert [r["scope"] for r in rows if r["role"] == "tp+pp"] == ["gt.optimizer"]


# the walk that `scan_grad_sums` was until PR 68 (PR 55's), kept here as what the rows are held to
def old_fused_sums(text):
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"%(all-reduce-scatter[\w.\-]*) \((.*)\) -> ", line)
        if head:
            name = head.group(1)
            out[name] = (C._shape_bytes(head.group(2)), None)
        elif name and line.startswith("}"):
            name = None
        elif name and " all-reduce(" in line:
            out[name] = (out[name][0], C.replica_groups(line))
    return out


def old_scan_grad_sums(text, dp_groups):
    fused, sums = old_fused_sums(text), []
    for line in text.splitlines():
        if not C.SCAN_BACKWARD.search(line):
            continue
        called = re.search(r"calls=%(all-reduce-scatter[\w.\-]*)", line)
        summed = re.search(r" (all-reduce|reduce-scatter)(?:-start)?\(", line)
        if called:
            kind, (sizes, groups) = "reduce-scatter", fused.get(called.group(1), ([], None))
        elif summed:
            kind, groups = summed.group(1), C.replica_groups(line)
            sizes = C._shape_bytes(line[:summed.start()].partition(" = ")[2])
            if kind == "reduce-scatter":
                sizes = [n * len(next(iter(groups))) for n in sizes] if groups else sizes
        else:
            continue
        if groups in dp_groups:
            sums += [(kind, n) for n in sizes if n > C.LARGE_OPERAND_BYTES]
    return sums


@pytest.mark.parametrize("name", list(CASES))
def test_the_scanned_gradients_sums_through_the_rows_are_the_old_walks(v5e_2x2, name):  # noqa: F811
    """tests/ops/test_scan_grad_sums.py's five layouts: the (kind, bytes) the
    one walk finds over dp in the backward scan's body are the ones PR 55's
    own walk of the text found, and the rows of role `dp` there hold them."""
    from galvatron_tpu.models.llama import llama_config

    flags, _, kinds = CASES[name]
    cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=1024, num_heads=8, ffn_hidden=2048,
                       vocab_size=32000, max_seq_len=256, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(4, 2, tp=2, vocab_tp=2, global_bsz=8, mixed_precision="bf16", **flags)
    model, step = _model_and_compiled_step(cfg, hp, v5e_2x2, batch_rows=8)
    text = step.as_text()
    dp = [C.axis_groups(model.mesh, layer_axes(hp, 0).dp)]
    walked = C.walk(text)
    assert sorted(C.scan_grad_sums(walked, dp)) == sorted(old_scan_grad_sums(text, dp)) != []
    assert {kind for kind, _ in C.scan_grad_sums(walked, dp)} == kinds
    rows = C.step_collectives(walked, model.mesh, hp)
    large = [r for r in rows if r["role"] == "dp" and r["kind"] in kinds and r["phase"] == "bwd"
             and r["scope"] in ("gt.attn.proj", "gt.mlp") and r["operand_bytes"] > C.LARGE_OPERAND_BYTES]
    # (a row sums a tuple's operands, so wo's gradient of exactly 1 MB may ride one: never less than the sums)
    mb = sum(C.dp_grad_sums_mb(walked, dp).values())
    assert mb <= sum(r["operand_bytes"] for r in large) / 1e6 <= mb + 1.1 * hp.chunks
    assert not [r for r in rows if not r["axes"] or r["role"] == "other"]


# ------------------------------------------------------------- the CPU's mesh
def train_args(tmp, world, *layout):
    from galvatron_tpu.cli.arguments import initialize_galvatron

    tele = str(tmp / ("run%d.jsonl" % world))
    return tele, initialize_galvatron(mode="train_dist", argv=[
        "--model_type", "llama", "--set_model_config_manually", "1", "--hidden_size", "64",
        "--num_attention_heads", "4", "--num_layers", "2", "--vocab_size", "128", "--seq_length", "32",
        "--mixed_precision", "bf16", "--global_train_batch_size", "4", "--train_iters", "2",
        "--world_size", str(world), "--telemetry", tele, *layout])


@pytest.fixture(scope="module")
def cpu_runs(devices8, tmp_path_factory):
    """Two steps of a tiny LLaMA through `cli train` under the four-chip
    cell's flags on four CPU devices, and on one: (summary, telemetry file)."""
    from galvatron_tpu.cli.train import train

    tmp = tmp_path_factory.mktemp("census")
    four = train_args(tmp, 4, "--global_tp_deg", "2", "--default_dp_type", "zero2", "--vocab_tp", "2",
                      "--checkpoint", "1")
    one = train_args(tmp, 1)
    return {4: (train(four[1]), four[0]), 1: (train(one[1]), one[0])}


def test_cli_train_on_four_cpu_devices_ends_with_the_census_and_says_it_as_it_compiles(cpu_runs):
    summary, tele = cpu_runs[4]
    census = summary["step_collectives"]
    rows = census["rows"]
    assert census["census_ms"] > 0 and len(rows) > 20 and all(set(r) == FIELDS for r in rows)
    assert {r["form"] for r in rows} == {"plain"}  # XLA:CPU fuses none and hides none
    assert {"dp", "tp"} <= {r["role"] for r in rows} <= {"dp", "tp", "dp+tp"}
    assert not [r for r in rows if not r["axes"] or r["role"] == "other"]
    events, errors = T.read_events(tele)
    assert errors == [] and "collectives" in T.EVENT_SCHEMAS["compile"][1]
    (compiled,) = [e for e in events if e["type"] == "compile"]
    # a sink listened as the step compiled (`--telemetry`): the event has the rows, from the walk that gave
    # it `dp_grad_*_mb`, and the run's end hands the same ones on and does not count again
    assert compiled["collectives"] == rows and "dp_grad_all_reduce_mb" in compiled
    (ended,) = [e for e in events if e["type"] == "run_end"]
    assert ended["summary"]["step_collectives"] == census


def test_cli_train_on_one_device_has_no_field_and_report_prints_nothing_new(cpu_runs, capsys):
    summary, tele = cpu_runs[1]
    assert "step_collectives" not in summary
    events, _ = T.read_events(tele)
    assert not [e for e in events if e["type"] == "compile" and "collectives" in e]
    assert R.run([tele]) == 0
    out = capsys.readouterr().out
    assert "collectives of the compiled step" not in out and "hidden:" not in out


def test_cli_report_prints_the_census_as_a_table(cpu_runs, capsys):
    summary, tele = cpu_runs[4]
    rows = summary["step_collectives"]["rows"]
    assert R.run([tele]) == 0
    out = capsys.readouterr().out.splitlines()
    start = next(i for i, line in enumerate(out) if line.startswith("collectives of the compiled step"))
    assert out[start].startswith("collectives of the compiled step: %d instructions, counted in " % len(rows))
    assert out[start + 1].split() == ["role", "kind", "form", "scope", "phase", "n", "operand", "MB", "wire", "MB"]
    groups = collections.Counter((r["role"], r["kind"], r["form"], r["scope"] or "-", r["phase"] or "-") for r in rows)
    table = out[start + 2:start + 2 + len(groups)]
    assert [tuple(line.split()[:5]) for line in table] == sorted(groups)
    assert [int(line.split()[5]) for line in table] == [groups[k] for k in sorted(groups)]
    wire = sum(r["wire_bytes"] for r in rows if r["role"] == "dp" and r["scope"] == "gt.param_gather")
    (gather,) = [line for line in table if line.split()[:4] == ["dp", "all-gather", "plain", "gt.param_gather"]]
    assert float(gather.split()[-1]) == pytest.approx(wire / 1e6, abs=0.005)
    assert out[start + 2 + len(groups)] == "  hidden: 0 of %d collectives, 0 %% of the wire bytes" % len(
        [r for r in rows if r["wire_bytes"]])
    # the rows stay out of the timeline's one line an event
    assert not [line for line in out if "collectives=[" in line]


def test_the_rows_in_a_compile_event_alone_are_printed_too():
    """A stream whose run never ended (no `run_end`): the table comes from the
    `compile` event, with a matmul that hides a gather among its rows."""
    row = dict(instruction="fusion.1", kind="all-gather", form="hidden", group=2, axes=["m1"], role="tp",
               operand_bytes=4e6, wire_bytes=4e6, scope="gt.mlp", phase="fwd")
    rows = [row, dict(row, instruction="all-reduce.2", kind="all-reduce", form="plain", wire_bytes=12e6),
            dict(row, instruction="async-collective-done", form="done", operand_bytes=0, wire_bytes=0)]
    sink = T.MemorySink()
    sink.emit("run_start", model="m", world_size=4)
    sink.emit("compile", trace_ms=1.0, collectives=rows)
    out = R.render(R.analyze(sink.events)).splitlines()
    assert "collectives of the compiled step: 3 instructions" in out
    assert "  hidden: 1 of 2 collectives, 25 % of the wire bytes" in out

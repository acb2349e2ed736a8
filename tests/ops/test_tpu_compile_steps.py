"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
whole train steps at the cells' sizes and layouts: memory, the vocabulary's split, ZeRO's copies, the head."""

import collections
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from galvatron_tpu.obs import forms
from galvatron_tpu.obs.compiled import axis_groups, replica_groups
from tests.ops.tpu_compile import REPO, _compile_train_step, _model_and_compiled_step, v5e_2x2  # noqa: F401  (the fixture)


V5E_HBM_BYTES = 15.75 * 2**30


def test_one_chip_7b_width_step_fits_v5e_hbm(v5e_2x2):
    """The train step chip_smoke.py runs (LLaMA-7B width, 2 layers, batch 2,
    seq 2048, bf16 compute, fp32 params + Adam) compiles for one v5e chip,
    holds the kernel, and its program fits the chip's 15.75 GiB."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    cfg = llama_config("llama-7b", num_layers=2, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=2, mixed_precision="bf16")
    compiled = _compile_train_step(cfg, hp, v5e_2x2[:1], batch_rows=2)
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, "%.2f GiB" % (total / 2**30)


@pytest.fixture(scope="module")
def tp2dp2_step(v5e_2x2):
    """The train step of a narrow LLaMA compiled for the described 2x2 under
    `--global_tp_deg 2 --vocab_tp 2 --default_dp_type zero2` (the layout of
    the four-chip benchmark cell), by Megatron-SP on or off: (model, step)."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    def compiled(sequence_parallel: bool):
        cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=512, num_heads=4,
                           ffn_hidden=1024, vocab_size=32000, max_seq_len=256,
                           compute_dtype=jnp.bfloat16)
        hp = HybridParallelConfig.uniform(
            4, 2, tp=2, vocab_tp=2, default_dp_type="zero2", global_bsz=4,
            mixed_precision="bf16", sequence_parallel=sequence_parallel)
        return _model_and_compiled_step(cfg, hp, v5e_2x2, batch_rows=4)

    return {sp: compiled(sp) for sp in (False, True)}


@pytest.fixture(scope="module")
def tp2dp2_step_hlo(tp2dp2_step):
    return {sp: step.as_text() for sp, (_, step) in tp2dp2_step.items()}


@pytest.mark.parametrize("sequence_parallel,summed_by",
                         [(False, "all-reduce"), (True, "all-reduce-scatter")],
                         ids=["all_reduce", "megatron_sp_sum_and_slice"])
def test_vocab_split_embedding_is_a_lookup_on_v5e(tp2dp2_step_hlo, sequence_parallel, summed_by):
    """Under `vocab_tp 2` the embedding is a masked local gather and one sum
    over tp (models/parts/embed_head.vocab_parallel_lookup), not a one-hot matmul: no
    `dot_general` carries the `gt.embed` scope, the forward holds one
    collective there (an all-reduce; under Megatron-SP the compiler fuses it
    with the slice into sequence shards, a `fusion` that calls
    `%all-reduce-scatter`), and nothing is permuted."""
    ops = []  # (opcode, op_name) of every instruction under the gt.embed scope
    for line in tp2dp2_step_hlo[sequence_parallel].splitlines():
        name = re.search(r'op_name="([^"]*gt\.embed[^"]*)"', line)
        code = re.search(r" ([a-z][a-z0-9-]*)\(", line.partition(" = ")[2])
        if name and code:
            fused_sum = "calls=%all-reduce-scatter" in line
            ops.append(("all-reduce-scatter" if fused_sum else code.group(1), name.group(1)))
    assert any(code in ("gather", "scatter") for code, _ in ops), ops
    assert not [o for o in ops if "dot_general" in o[1] or o[0] in ("dot", "convolution")], ops
    assert not [o for o in ops if o[0].startswith("collective-permute")], ops
    forward_sums = [code for code, name in ops if "transpose(" not in name
                    and re.fullmatch(r"(all-reduce|reduce-scatter|all-reduce-scatter)(-start)?", code)]
    assert forward_sums == [summed_by], ops


@pytest.mark.parametrize("sequence_parallel", [False, True], ids=["tp2dp2", "tp2dp2_megatron_sp"])
def test_the_split_table_stays_where_zero2_updates_it_on_v5e(tp2dp2_step, sequence_parallel):
    """The looked-up table is stored `P(tp, dp)` (runtime/model_api
    state_specs) and what crosses dp is the lookup's: no all-gather,
    all-reduce or reduce-scatter (alone or fused) has an operand or a result
    of the table's float32 shapes, whole (vocab/tp, hidden) or split (vocab/tp,
    hidden/dp); the step holds the ids' gather and the `all_to_all` pair under
    `gt.embed`, (B, S, H/dp) rows forward and (B/dp, S, H) cotangents back; and
    the table goes in and comes out split."""
    model, step = tp2dp2_step[sequence_parallel]
    cfg, text = model.cfg, step.as_text()
    spec = model.table_spec()
    assert spec == model.grad_accum_specs()["embed"]["wte"] != model.param_specs["embed"]["wte"]
    rows, hidden = cfg.vocab_size // 2, cfg.hidden_size
    table_shapes = [r"f32\[%d,%d\]" % (rows, h) for h in (hidden, hidden // 2)]
    sums_and_gathers = re.compile(
        r" (all-gather|all-reduce|reduce-scatter)(-start)?\(|calls=%(all-reduce-scatter|all-gather|reduce-scatter)")
    moved = [line.strip()[:160] for line in text.splitlines()
             if sums_and_gathers.search(line) and any(re.search(t, line) for t in table_shapes)]
    assert not moved, moved
    exchanged = [(m.group(1), "transpose(" in line) for line in text.splitlines()
                 if "gt.embed" in line and (m := re.search(r" = bf16\[([\d,]+)\]\S* all-to-all\(", line))]
    assert sorted(exchanged) == sorted([("4,256,%d" % (hidden // 2), False), ("2,256,%d" % hidden, True)]), exchanged
    assert [line for line in text.splitlines()
            if "gt.embed" in line and re.search(r" = s32\[[\d,]+\]\S* all-gather\(", line)]
    table_in = step.input_shardings[0][0]["embed"]["wte"]
    assert table_in.is_equivalent_to(NamedSharding(model.mesh, spec), 2)
    assert step.output_shardings[0]["embed"]["wte"].is_equivalent_to(table_in, 2)


def test_the_cpu_step_of_that_layout_prints_no_reduce_scatter(devices8):
    """XLA:CPU has no reduce-scatter of its own choice, and the benchmark's
    CPU rehearsal of the four-chip cell counts on none
    (tests/benchmarks/test_cell_from_files.py NOT_ON_THE_CPU): the lookup's
    second form is written without `psum_scatter`."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=64, num_heads=4, ffn_hidden=128,
                       vocab_size=256, max_seq_len=32, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(4, 2, tp=2, vocab_tp=2, default_dp_type="zero2", global_bsz=4,
                                      mixed_precision="bf16", checkpoint=1)
    model, step = _model_and_compiled_step(cfg, hp, devices8[:4], batch_rows=4)
    text = step.as_text()
    assert model.table_spec() != model.param_specs["embed"]["wte"]
    assert "reduce-scatter" not in text and " all-to-all(" in text


@pytest.mark.parametrize("sequence_parallel", [False, True], ids=["tp2dp2", "tp2dp2_megatron_sp"])
def test_zero2_gathers_a_bf16_copy_and_stores_float32_shards_on_v5e(tp2dp2_step, sequence_parallel):
    """ZeRO-2's compute copy in the compiled step (runtime/model_api
    compute_params). Over the dp groups, every bf16 all-gather carries
    `gt.param_gather` and gathers a copied leaf, each copied leaf at least
    once; the float32 all-gathers left are the norm scales', after the update
    and under no scope (the `vocab_tp` table, looked up from the stored shard,
    is stored split too and nothing gathers it: the test below); nothing under
    `gt.param_gather` is float32. The parameters go in
    and come out in one layout, leaf by leaf: one compilation, donated
    buffers reused."""
    from galvatron_tpu.parallel.mesh import vocab_axes

    model, step = tp2dp2_step[sequence_parallel]
    vax = vocab_axes(model.hp)
    dp_groups = axis_groups(model.mesh, vax.dp)
    assert dp_groups == {frozenset({0, 2}), frozenset({1, 3})}

    gathered = {"bf16": [], "f32": []}  # (elements a chip, op_name) of the dp all-gathers
    for line in step.as_text().splitlines():
        out = re.search(r" = \(?(?:(?:bf16|f32)\[[\d,]*\]\S*(?:, )?)+\)? all-gather(?:-start)?\(", line)
        if not out or replica_groups(line) != dp_groups:
            continue
        shapes = re.findall(r"(bf16|f32)\[([\d,]*)\]", out.group(0))
        name = re.search(r'op_name="([^"]*)"', line)
        for dtype, dims in shapes[len(shapes) // 2 if "all-gather-start" in out.group(0) else 0:]:
            gathered[dtype].append((int(np.prod([int(d) for d in dims.split(",")])),
                                    name.group(1) if name else ""))

    tp = int(np.prod([model.mesh.shape[a] for a in vax.tp]))
    shapes = model.abstract_params()
    sizes = {True: [], False: []}  # elements a chip of the leaves ZeRO-2 splits, copied or not
    jax.tree.map(
        lambda copied, spec, split, a: sizes[copied].append(
            a.size // (tp if any(e is not None for e in spec) else 1)) if split != spec else None,
        model.copied_leaves(), model.param_specs, model.grad_accum_specs(), shapes,
        is_leaf=lambda x: isinstance(x, P))
    assert sizes[True] and all("gt.param_gather" in name for _, name in gathered["bf16"])
    assert sorted({n for n, _ in gathered["bf16"]}) == sorted(set(sizes[True]))
    assert sum(n for n, _ in gathered["bf16"]) >= sum(sizes[True])
    # float32: the norm scales, under no scope; of the leaves ZeRO-2 splits
    # without a copy the table's rows a chip are the other, and stay split
    table = shapes["embed"]["wte"].size // tp
    assert {n for n, _ in gathered["f32"]} == {model.cfg.hidden_size}
    assert sorted(set(sizes[False])) == sorted({table, model.cfg.hidden_size})
    assert not [name for _, name in gathered["f32"] if "gt." in name]

    ins, outs = jax.tree.leaves(step.input_shardings[0][0]), jax.tree.leaves(step.output_shardings[0])
    wanted = jax.tree.leaves(model.shardings())
    assert len(ins) == len(outs) == len(wanted)
    for a, i, o, w in zip(jax.tree.leaves(shapes), ins, outs, wanted):
        assert i.is_equivalent_to(o, a.ndim) and i.is_equivalent_to(w, a.ndim), (a.shape, i, o, w)


def _cell_model_and_step(workload, devices):
    """A benchmark cell's train step at its own size, compiled for `devices`
    from the cell's own files and flags: (model, step)."""
    from benchmarks import cells
    from galvatron_tpu.cli.arguments import hp_config_from_args, initialize_galvatron, model_config_from_args

    cell = cells.load_cell(REPO, workload)
    cells.register_family(cell)
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, 0))
    _, cfg = model_config_from_args(args)
    assert cfg.max_seq_len == cell.traffic["seq_length"]
    return _model_and_compiled_step(cfg, hp_config_from_args(args, cfg.num_layers, cell.chips), devices,
                                    batch_rows=cell.traffic["global_batch"])


def test_the_four_chip_cell_sums_its_scanned_gradients_into_zeros_shards_on_v5e(v5e_2x2):
    """`qwen7-c4-tp2dp2` at its own size (four layers at Qwen2.5-7B's widths,
    tp 2 x dp 2, ZeRO-2; about a minute): the one scanned run asks for the
    cotangent of its nine stacked leaves in ZeRO's layout (two norm scales, q
    and k/v with a bias each, wo, wi, wo_mlp), and what the `compile` event
    then reads off the compiled step (cli/train._scan_grad_sums_mb): no
    weight gradient over 1 MB is all-reduced over the dp pairs inside the
    backward scan's body, and the layer's five kernels, 233.0 MB a chip in
    bf16, go through reduce-scatters there (before PR 55: 233.0 all-reduced,
    0 reduce-scattered, and the step kept half of the sum afterwards)."""
    from galvatron_tpu.cli.train import _scan_grad_sums_mb
    from galvatron_tpu.obs import telemetry

    with forms.recording() as took:
        model, step = _cell_model_and_step("qwen7-c4-tp2dp2", v5e_2x2)
    assert took[forms.SCAN_GRADS] == {"zero_layout": 9}
    h, f, heads, kv, d = 3584, 18944, 28, 4, 128
    kernels = 2 * (h * heads * d + h * 2 * kv * d + heads * d * h + h * 2 * f + f * h) // 2  # bf16, a tp half
    assert _scan_grad_sums_mb(model, step) == {}  # nobody listens: the step's text is not printed
    sink = telemetry.install(telemetry.MemorySink())
    try:
        assert _scan_grad_sums_mb(model, step) == {
            "dp_grad_all_reduce_mb": 0.0, "dp_grad_reduce_scatter_mb": kernels / 1e6}
    finally:
        telemetry.uninstall(sink)
    assert round(kernels / 1e6, 1) == 233.0


@pytest.fixture(scope="module")
def pp2tp2_cell_step(v5e_2x2):
    """The pipelined benchmark cell `qwen7-c4-pp2tp2` at its own size (four
    layers at Qwen2.5-7B's widths, 8 x 2048 tokens, pp2 x tp2, GPipe, 4
    microbatches, `--vocab_tp 2`) compiled for the described 2x2 from the
    cell's own files and flags: (model, step). About half a minute."""
    return _cell_model_and_step("qwen7-c4-pp2tp2", v5e_2x2)


def test_the_pipelined_cell_splits_its_vocabulary_over_pp_on_v5e(pp2tp2_cell_step):
    """The scan pipeline's vocabulary layers take the pp axis
    (`mesh.pipeline_vocab_axes`): the table and the head go in and come out
    split over pp x vocab_tp, a quarter of each a chip; the step holds under
    9.6 GiB a chip (14.78 while every stage held and computed a whole tp-half
    of both: PERF.md, PR 54); and NO collective of the compiled step has an
    operand of a table's size or of a quarter, a half of it: what crosses pp
    for these layers is activations (the lookup's sum, the head's input
    gradient, the loss's maximum and sum)."""
    model, step = pp2tp2_cell_step
    cfg = model.cfg
    split = P(("pp", "m0"), None)
    assert model.param_specs["embed"]["wte"] == model.table_spec() == split
    assert model.param_specs["lm_head"]["kernel"] == P(None, ("pp", "m0"))
    table_in = step.input_shardings[0][0]["embed"]["wte"]
    assert table_in.is_equivalent_to(NamedSharding(model.mesh, split), 2)
    assert step.output_shardings[0]["embed"]["wte"].is_equivalent_to(table_in, 2)

    ma = step.memory_analysis()
    total = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert total < 9.6 * 2**30, "%.3f GiB" % (total / 2**30)

    table = cfg.vocab_size * cfg.hidden_size
    kinds = collections.Counter()
    for line in step.as_text().splitlines():
        op = re.search(r" = (.*?) (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)(?:-start)?\(", line)
        if not op:
            continue
        kinds[op.group(2)] += 1
        for dims in re.findall(r"\w+\[([\d,]+)\]", op.group(1)):
            n = int(np.prod([int(d) for d in dims.split(",")]))
            assert n not in (table, table // 2, table // 4), line[:300]
            # (the largest is the embedded batch, whole: 8 x 2048 x 3584 in bf16)
            assert n <= 5 * 2 * 2048 * cfg.hidden_size, line[:300]
    assert kinds["all-reduce"] and kinds["collective-permute"] and "tpu_custom_call" in step.as_text()


def _head_fusions():
    """scripts/head_fusions.py, which reads a compiled step's operations under a scope."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "head_fusions", os.path.join(REPO, "scripts", "head_fusions.py"))
    head_fusions = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(head_fusions)
    return head_fusions


@pytest.fixture(scope="module")
def one_chip_head_ops(v5e_2x2):
    """The operations under `gt.head_loss` of a narrow LLaMA's train step
    (float32 parameters, bf16 compute, an untied (512, 32000) head) compiled
    for one described chip, as `scripts/head_fusions.py` lists them."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=512, num_heads=4, ffn_hidden=1024,
                       vocab_size=32000, max_seq_len=256, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=4, mixed_precision="bf16")
    return _head_fusions().head_ops(_compile_train_step(cfg, hp, v5e_2x2[:1], batch_rows=4).as_text())


def test_the_heads_matmuls_read_one_bf16_kernel_on_v5e(one_chip_head_ops):
    """models/parts/embed_head._head_matmul in the compiled step: one operation under
    `gt.head_loss` reads the float32 head kernel, the cast, which no matmul
    holds; forward, input gradient and kernel gradient read or write the bf16
    (hidden, V) copy; and the input gradient's fusion writes the input
    gradient alone, the final norm's backward reading it afterwards. Without
    the rule the compiler folds the cast into each matmul's fusion, redoing
    it a tile of tokens, and the norm's reductions into the input gradient's
    (PERF.md, PR 30)."""
    wide, narrow = "f32[512,32000]", "bf16[512,32000]"
    readers = [o for o in one_chip_head_ops if wide in o["operands"]]
    assert len(readers) == 1 and not readers[0]["matmul"] and readers[0]["out"] == [narrow], readers
    matmuls = [o for o in one_chip_head_ops if o["matmul"]]
    assert [o["backward"] for o in matmuls] == [False, True, True], matmuls
    assert all(narrow in o["operands"] + o["out"] for o in matmuls), matmuls
    assert [o["out"] for o in matmuls if o["backward"] and narrow in o["operands"]] == [["bf16[4,256,512]"]]


def test_the_cross_entropy_sweeps_the_logits_once_each_way_on_v5e(one_chip_head_ops):
    """models/parts/embed_head._token_nll in the compiled step: `exp` runs in the
    forward's one sweep of the logits (sum of exponentials and the label's
    logit together) and where the backward's two matmuls form the softmax
    gradient as they read the logits; no pass of the backward exists only to
    differentiate the row maximum (autodiff's second sweep: a fourth `exp`)."""
    with_exp = [o for o in one_chip_head_ops if o["exp"]]
    assert len(with_exp) <= 3 and sum(o["exp"] for o in with_exp) <= 3, with_exp
    assert [o["matmul"] for o in with_exp if not o["backward"]] == [False], with_exp
    assert all(o["matmul"] for o in with_exp if o["backward"]), with_exp


def test_a_gelu_is_written_out_in_the_forward_alone_on_v5e(v5e_2x2):
    """models/parts/mlp.dense_mlp's two barriers in the compiled step: one
    layer at Cerebras-GPT-6.7B's widths (hidden 4096, ffn 16384, the exact
    GELU) under `--checkpoint 1`, 256 tokens (the compiler folds the GELU into
    the down projection at any batch; about 8 s). In the FORWARD the `erf`
    (its `exponential`) is a fusion of its own and no fusion nested in either
    projection's holds one: both matmuls read and write arrays (the down
    projection 47 % of the MXU -> 94 %, PERF.md section 6, PR 67). The
    RECOMPUTED up projection writes ONE array of the activation's size, as
    without the rules: a barrier on the activation's VALUE is in the
    recomputation too, which then writes the activation beside the
    pre-activation (`step_hbm_gib` + 10.6 % in `gpt67-c1-s2k`, ISSUE 67)."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.gpt import gpt_config

    tokens, ffn = 256, 16384
    cfg = gpt_config("gpt-6.7b", num_layers=1, hidden_size=4096, num_heads=32, head_dim=128, ffn_hidden=ffn,
                     vocab_size=1024, max_seq_len=tokens, activation="gelu_exact", compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(1, 1, checkpoint=1, global_bsz=1, mixed_precision="bf16")
    with forms.recording() as took:
        step = _compile_train_step(cfg, hp, v5e_2x2[:1], batch_rows=1)
    assert set(took[forms.MLP_ACTIVATION]) == {"written_out"}
    ops = _head_fusions().head_ops(step.as_text(), scope="gt.mlp")
    up, down = [o for o in ops if o["matmul"] and not o["backward"]]
    assert up["op_name"].endswith("->bs.../dot_general") and down["op_name"] == "dot_general", (up, down)
    assert (up["exp"], down["exp"]) == (0, 0), (up, down)
    assert [o["exp"] for o in ops if o["exp"] and not o["backward"]] == [1], ops
    recomputed, = [o for o in ops if o["matmul"] and o["recomputed"]]
    activation_sized = [s for s in recomputed["out"]
                        if np.prod([int(d) for d in re.findall(r"\d+", s.partition("[")[2])]) == tokens * ffn]
    assert recomputed["op_name"].endswith("->bs.../dot_general") and len(activation_sized) == 1, recomputed

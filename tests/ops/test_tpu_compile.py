"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached).

libtpu compiles for a topology that is described, not attached
(`jax.experimental.topologies`), so what the chip's compiler would refuse —
a kernel it cannot tile, a Mosaic call GSPMD would have to partition, a step
that does not fit HBM — is refused here, on the CPU box, at no chip time.
Nothing runs: these say nothing about results or speed (chip_smoke.py does).
The persistent compilation cache is off around them: an entry written for a
described device cannot be read back without one, and the next compile would
warn and compile again.
"""

import collections
import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.obs.compiled import axis_groups, replica_groups
from galvatron_tpu.ops import attention as A

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
V5E_HBM_BYTES = 15.75 * 2**30

B, S, NH, HD = 2, 2048, 32, 128  # LLaMA-7B attention, batch cut to 2


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host
        pytest.skip("cannot describe a TPU topology here: %s" % e)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _qkv(sharding):
    return jax.ShapeDtypeStruct((B, S, NH, HD), jnp.bfloat16, sharding=sharding)


def _attn_loss(sharding):
    """Causal flash attention loss; an optional 4th operand is a key-padding
    bias, which rides the kernel as segment ids."""
    def loss(q, k, v, *b):
        out = A.core_attention(q, k, v, causal=True, impl="flash", sharding=sharding,
                               bias=b[0] if b else None, bias_type="key_padding")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles_for_v5e(v5e_2x2, backward):
    """The repo's block sizes (1024 x 512) at 7B attention shapes, causal."""
    one = SingleDeviceSharding(v5e_2x2[0])
    fn = _attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",))))
    if backward:
        fn = jax.grad(fn, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(one), _qkv(one), _qkv(one)).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_kernel_compiles_at_granites_64_wide_heads_for_v5e(v5e_2x2):
    """32 query heads on 8 KV heads of 64 at 4096 tokens, forward and the two
    backward kernels, through `impl="auto"`: Mosaic takes the 64-wide heads as
    they are, so the dispatch pads nothing and never falls to XLA's (b, nh, s,
    s) float32 logits (2 GiB at these shapes)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    shd = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))

    def loss(q, k, v):
        out = A.core_attention(q, k, v, causal=True, sm_scale=0.015625, sharding=shd)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16, sharding=one)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") >= 3 and "f32[1,32,4096,4096]" not in text


def test_flash_segment_id_form_compiles_for_v5e(v5e_2x2):
    """A key-padding bias rides the kernel as segment ids (forward+backward)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    bias = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32, sharding=one)
    fn = jax.grad(_attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))),
                  argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(one), _qkv(one), _qkv(one), bias).compile().as_text()
    assert "tpu_custom_call" in text


def test_sharded_flash_kernel_compiles_on_2x2_mesh(v5e_2x2):
    """Batch over 2, heads over 2: under GSPMD alone this raises 'Mosaic
    kernels cannot be automatically partitioned'; the manual region
    (ops/attention.KernelSharding) gives each chip its own rows and heads,
    and needs no collective to do so."""
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 2, 2), ("pp", "m0", "m1"))
    sh = NamedSharding(mesh, P("m0", None, "m1", None))
    fn = jax.grad(_attn_loss(A.KernelSharding(mesh, ("m0",), ("m1",))), argnums=(0, 1, 2))
    text = jax.jit(fn).lower(_qkv(sh), _qkv(sh), _qkv(sh)).compile().as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert collective not in text, collective


def test_auto_dispatch_reads_the_platform_off_the_mesh(v5e_2x2):
    """impl='auto' on a described-TPU mesh takes the kernel although this
    process's default backend is the CPU — the branch the chip takes."""
    assert jax.default_backend() == "cpu"
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "m0"))
    sh = NamedSharding(mesh, P("m0", None, None, None))
    shd = A.KernelSharding(mesh, ("m0",), ())

    def fwd(q, k, v):
        return A.core_attention(q, k, v, causal=True, sharding=shd)

    q = jax.ShapeDtypeStruct((4, S, NH, HD), jnp.bfloat16, sharding=sh)
    assert "tpu_custom_call" in jax.jit(fwd).lower(q, q, q).compile().as_text()


def _compile_train_step(cfg, hp, devices, batch_rows):
    """The model's train step (Adam) compiled for `devices` from shapes alone."""
    return _model_and_compiled_step(cfg, hp, devices, batch_rows)[1]


def _model_and_compiled_step(cfg, hp, devices, batch_rows):
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    m = construct_hybrid_parallel_model(cfg, hp, devices)
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-4, warmup_steps=0, total_steps=8))

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    params = m.abstract_params()
    opt = jax.eval_shape(tx.init, params)
    tok = jax.ShapeDtypeStruct((batch_rows, cfg.max_seq_len), jnp.int32)
    batch = {k: jax.ShapeDtypeStruct(tok.shape, tok.dtype,
                                     sharding=NamedSharding(m.mesh, m._batch_spec_for(tok)))
             for k in ("tokens", "positions", "labels")}
    return m, m.make_train_step(tx).lower(
        sds(params, m.shardings()), sds(opt, m.opt_state_shardings(tx, params)), batch,
    ).compile()


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
    from galvatron_tpu.models import base as M
    from galvatron_tpu.obs import telemetry

    before = sum(M.SCAN_GRADS_IN_ZERO_LAYOUT.values())
    model, step = _cell_model_and_step("qwen7-c4-tp2dp2", v5e_2x2)
    assert sum(M.SCAN_GRADS_IN_ZERO_LAYOUT.values()) - before == 9
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


@pytest.fixture(scope="module")
def one_chip_head_ops(v5e_2x2):
    """The operations under `gt.head_loss` of a narrow LLaMA's train step
    (float32 parameters, bf16 compute, an untied (512, 32000) head) compiled
    for one described chip, as `scripts/head_fusions.py` lists them."""
    import importlib.util

    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    spec = importlib.util.spec_from_file_location(
        "head_fusions", os.path.join(REPO, "scripts", "head_fusions.py"))
    head_fusions = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(head_fusions)
    cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=512, num_heads=4, ffn_hidden=1024,
                       vocab_size=32000, max_seq_len=256, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=4, mixed_precision="bf16")
    return head_fusions.head_ops(_compile_train_step(cfg, hp, v5e_2x2[:1], batch_rows=4).as_text())


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


def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py on the CPU exits non-zero before any work and prints no
    verdict — a measurement path that finds no chip fails, it does not fall
    back."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip() == "", proc.stdout


# ------------------------------------------------------------ routed experts
MOE_TOKENS, MOE_H, MOE_F, MOE_E, MOE_K = 8192, 2048, 1024, 64, 8  # OLMoE-1B-7B, 2 x 4096


MEGABLOX_CALL = r"%t?gmm[.\d]* = "  # the grouped matmul and its kernels' gradient


def _moe_loss(sharding):
    from galvatron_tpu.ops.moe import moe_ffn

    def loss(y, router, wi, wo):
        out, aux = moe_ffn(y, router, wi, wo, experts_per_token=MOE_K, dtype=y.dtype,
                           sharding=sharding)
        return jnp.sum(out.astype(jnp.float32) ** 2) + aux["load_balance"] + aux["router_z"]

    return loss


def _moe_operands(batch, tokens_sharding, whole, dtype=jnp.bfloat16):
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((batch, MOE_TOKENS // 2, MOE_H), dtype, sharding=tokens_sharding),
            jax.ShapeDtypeStruct((MOE_H, MOE_E), f32, sharding=whole),
            jax.ShapeDtypeStruct((MOE_E, MOE_H, 2 * MOE_F), f32, sharding=whole),
            jax.ShapeDtypeStruct((MOE_E, MOE_F, MOE_H), f32, sharding=whole))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "float32"])
def test_the_routed_experts_block_compiles_for_v5e(v5e_2x2, dtype):
    """ops/moe.py at OLMoE's widths, forward and backward, one chip: on a TPU
    (read off the mesh, as the flash kernel's dispatch) the grouped matmuls are
    the megablox kernels at the measured tiling, which the chip's compiler
    takes (float32 operands at half the K and N tiles: the whole ones exceed
    the scoped VMEM); off it, `ragged_dot`."""
    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))
    fn = jax.grad(_moe_loss(on_chip), argnums=(0, 1, 2, 3))
    text = jax.jit(fn).lower(*_moe_operands(2, one, one, dtype)).compile().as_text()
    assert len(re.findall(MEGABLOX_CALL, text)) == 6  # 2 forward, 4 backward
    assert "ragged-dot" not in text
    if dtype == jnp.float32:
        return
    off_chip = jax.jit(_moe_loss(None)).lower(*_moe_operands(2, one, one)).compile().as_text()
    assert "ragged-dot" in off_chip and not re.findall(MEGABLOX_CALL, off_chip)


def test_the_routed_experts_block_is_a_manual_region_on_a_dp4_mesh(v5e_2x2):
    """Under dp the block runs per device on its own batch rows against whole
    experts (a region manual over every axis, as the flash kernel's), and the
    only collectives are the sums of the router's statistics and of the
    parameters' gradients."""
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "dp"))
    sharding = A.KernelSharding(mesh, batch_axes=("dp",))
    fn = jax.grad(_moe_loss(sharding), argnums=(0, 1, 2, 3))
    text = jax.jit(fn).lower(*_moe_operands(
        8, NamedSharding(mesh, P("dp", None, None)), NamedSharding(mesh, P()))).compile().as_text()
    assert len(re.findall(MEGABLOX_CALL, text)) == 6
    assert "all-reduce" in text and "all-to-all" not in text and "all-gather" not in text


# ------------------------- GLM-4.7-Flash: the kernels' shapes new with PR 32
def test_flash_kernel_compiles_at_head_dim_256_for_v5e(v5e_2x2):
    """Latent attention calls the kernel once at q/k = v = 256 dims a head (20
    heads, 8192 tokens): the repo's 1024 x 512 blocks, which every other cell
    runs at 128, still fit the scoped VMEM at twice the width in bf16 (in
    float32 they do not: a float32 program of this family takes XLA's path)."""
    one = SingleDeviceSharding(v5e_2x2[0])
    operand = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.bfloat16, sharding=one)
    fn = jax.grad(_attn_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))),
                  argnums=(0, 1, 2))
    text = jax.jit(fn).lower(operand, operand, operand).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # forward, dkv, dq
    assert "block_q_1024" in text and "block_k_512" in text.replace("block_k_major_512", "block_k_512")


GLM_TOKENS, GLM_H, GLM_WIDTH, GLM_EXPERTS, GLM_HELD = 8192, 2048, 1536, 64, 8  # the cell glm47f-c1-s8k


def _held_share(v5e_2x2, k):
    """ops/moe.py at GLM-4.7-Flash's widths with 8 of the 64 experts held and
    `k` a token: the loss, its operands' shapes, forward + backward compiled."""
    from galvatron_tpu.ops.moe import moe_ffn

    one = SingleDeviceSharding(v5e_2x2[0])
    on_chip = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))

    def loss(y, router, bias, wi, wo):
        out, aux = moe_ffn(y, router, wi, wo, experts_per_token=k, norm_topk_prob=True,
                           dtype=y.dtype, sharding=on_chip, score="sigmoid", bias=bias,
                           scale=1.8, held=(16, GLM_HELD))
        return jnp.sum(out.astype(jnp.float32) ** 2), aux

    f32 = jnp.float32
    operands = (jax.ShapeDtypeStruct((1, GLM_TOKENS, GLM_H), jnp.bfloat16, sharding=one),
                jax.ShapeDtypeStruct((GLM_H, GLM_EXPERTS), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_EXPERTS,), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_HELD, GLM_H, 2 * GLM_WIDTH), f32, sharding=one),
                jax.ShapeDtypeStruct((GLM_HELD, GLM_WIDTH, GLM_H), f32, sharding=one))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4), has_aux=True)).lower(*operands).compile()
    return loss, operands, compiled.as_text()


@pytest.fixture(scope="module")
def held_share(v5e_2x2):
    return functools.cache(lambda k: _held_share(v5e_2x2, k))  # one compile a k


def _branches(text, index):
    """The instructions of branch `index` of every `conditional` in a compiled
    text: 1 is `jax.lax.cond`'s true branch (a share's window), 0 its false
    one (the whole range)."""
    computations, name = {}, None
    for line in text.splitlines():
        start = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if start:
            name = start.group(1)
        elif name is not None:
            computations.setdefault(name, []).append(line.strip())
    taken = [re.findall(r"%([\w.\-]+)", found)[index]
             for found in re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}", text)]
    return [line for name in taken for line in computations[name]]


def test_a_held_share_of_the_experts_compiles_for_v5e(held_share):
    """Forward and backward: the sigmoid router with its bias ranks all 64,
    the megablox kernels take the held groups' offset (`group_offset`) and the
    kernels of 8 experts, and the counters come back. Each direction is a
    `cond` of the window and the whole range, and the backward makes the
    experts' forward again: 2 + 6 calls a branch, and in the whole range's the
    up projection a third time, after the combine's backward."""
    loss, operands, text = held_share(4)
    assert "ragged-dot" not in text
    assert [len(re.findall(MEGABLOX_CALL, "\n".join(_branches(text, index)))) for index in (0, 1)] == [9, 8]
    aux = jax.eval_shape(loss, *operands)[1]
    assert set(aux) == {"load_max_over_mean", "counts", "bias_abs_max", "rows_held", "window_fallbacks"}
    assert aux["counts"].shape == (GLM_EXPERTS,)


@pytest.mark.parametrize("k", [4, 6, 8])
def test_the_routed_block_keeps_k_out_of_the_tiles_on_v5e(held_share, k):
    """A TPU tiles an array's two minor dimensions by 8 x 128, so a (tokens, k,
    hidden) array whose k is not a multiple of 8 is padded to one, every
    reshape to or from (tokens x k, hidden) moves every row, and the compiler
    stops fusing across it: float32 copies of all rows, a broadcast of the
    cotangent written out (PERF.md, PR 34). `ops/moe.py` keeps the assignments
    k-major and sums over k slab by slab, so between the gathers and the sums
    of dispatch and combine nothing of the kind is left, whatever k is. A
    reshape that survives to the compiled text is a physical one."""
    from galvatron_tpu.obs import tracing

    text = held_share(k)[2]
    every_row = GLM_TOKENS * k * GLM_H
    ops = [line for line in _branches(text, 1)  # the window's branch of both directions
           if re.search(r'op_name="[^"]*(%s|%s)' % (re.escape(tracing.MOE_COMBINE),
                                                     re.escape(tracing.MOE_DISPATCH)), line)]
    assert len(ops) > 10 and any(re.search(r"transpose\(.*%s" % re.escape(tracing.MOE_COMBINE), op) for op in ops)
    offenders = []
    for op in ops:
        name, result, kind = re.match(r"(\S+) = (.*?[})]) ([a-z\-]+)\(", op).groups()
        sizes = [(dtype, int(np.prod([int(d) for d in dims.split(",") if d])))
                 for dtype, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]*)\]", result)]
        if (kind == "reshape" or (kind == "broadcast" and every_row in [n for _, n in sizes])
                or ("f32", every_row) in sizes):
            offenders.append("%s = %s %s" % (name, result, kind))
    assert not offenders, "\n".join(offenders)


def _scope(op_name):
    """The innermost `gt.` scope of an op's name."""
    return re.findall(r"gt\.[a-z_.]+", op_name)[-1]


def test_the_routed_blocks_rows_move_by_dma_on_v5e(held_share):
    """PR 40: on a TPU, at bf16 rows of 2048 and whole grid steps, the sum
    over k of the combine's forward and of the dispatch's backward and the
    combine's backward are the row movers (`ops/moe.rows_form`): under
    `gt.moe.combine` and `gt.moe.dispatch` the step has their custom calls,
    each fed by a packing pass, and XLA gathers (k x tokens, hidden) rows in
    the dispatch's own forward alone, whose small source it keeps in fast
    memory (PERF.md, PR 40: the sweep); once a direction, since a share's
    backward makes its forward again (read off the window's branch of each
    `cond`: the whole range's is the same block)."""
    from galvatron_tpu.obs import tracing

    text = "\n".join(_branches(held_share(4)[2], 1))
    calls = dict.fromkeys(("moe_rows_pack", "moe_rows_back", "moe_rows_out"), ())
    for line in text.splitlines():
        found = re.search(r'custom_call_target="tpu_custom_call".*op_name="([^"]*)/(moe_rows_\w+)/pallas_call"', line)
        if found:
            calls[found.group(2)] += (found.group(1),)
    combine, dispatch = tracing.MOE_COMBINE, tracing.MOE_DISPATCH
    assert sorted(_scope(op) for op in calls["moe_rows_back"]) == [combine, dispatch], calls
    assert [_scope(op) for op in calls["moe_rows_out"]] == [combine], calls
    assert sorted(_scope(op) for op in calls["moe_rows_pack"]) == [combine, combine, dispatch], calls
    every_row = r"bf16\[%d,%d\]" % (4 * GLM_TOKENS, GLM_H)
    gathers = [line for line in text.splitlines()  # inside a branch a gather is a fusion of its own
               if re.search(r"= %s\S* (gather|fusion)\(" % every_row, line)
               and re.search(r'op_name="[^"]*(%s|%s)[^"]*/gather"' % (re.escape(combine), re.escape(dispatch)), line)]
    assert len(gathers) == 2 and all(re.search(r'%s/gather"' % re.escape(dispatch), line) for line in gathers), gathers


def test_a_shares_experts_work_on_a_window_of_the_rows_on_v5e(held_share, v5e_2x2):
    """PR 47: with a share of the experts held, everything under
    `gt.moe.experts` in the window's branch of both directions (the grouped
    matmuls, megablox's fill of the rows it skips, the activation and its
    backward) runs over `window_rows` rows: nothing there has an array as long
    as the `k x tokens` assignments but the rows it cuts its window from and
    the zeros it lays its result into. The whole-range branch beside it does,
    which is what the window is for. And the block's two rules are traced
    and lowered once a shape (`jax.jit`), whatever the number of layers:
    the forward rule's two kernels, the backward's six, for the window and
    for the whole range."""
    from galvatron_tpu.obs import tracing
    from galvatron_tpu.ops import moe

    loss, operands, text = held_share(4)
    every, window = 4 * GLM_TOKENS, moe.window_rows(4 * GLM_TOKENS, GLM_EXPERTS, (16, GLM_HELD))
    assert window == 6656  # 1.5 x 4096 in 512-row tiles, and a tile

    def long_ops(index):
        """(what made it, the rows of its result) of the experts' ops in a branch whose result is as long as the assignments"""
        found = []
        for line in _branches(text, index):
            name = re.search(r'op_name="([^"]*%s[^"]*)"' % re.escape(tracing.MOE_EXPERTS), line)
            result = re.match(r"(?:ROOT )?\S+ = (.*?) [a-z\-]+\(", line)
            if name and result and re.search(r"\[%d,\d+\]" % every, result.group(1)):
                found.append(name.group(1).rsplit("/", 1)[-1])
        return sorted(set(found))

    assert long_ops(1) == ["dynamic_update_slice"], long_ops(1)  # laid into zeros, in place
    assert {"select_n", "mul", "pallas_call"} <= set(long_ops(0)), long_ops(0)
    windowed = "\n".join(line for line in _branches(text, 1) if tracing.MOE_EXPERTS in line)
    assert re.search(r"\[%d,\d+\]\S* fusion\(.*select_n" % window, windowed)  # the fill, over the window
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4), has_aux=True)).lower(*operands).as_text()
    kernels = re.findall(r"func\.func private @(t?gmm)\w*\((.*?)\) ->", lowered)
    shapes = collections.Counter((name, tuple(re.findall(r"tensor<([\dx]+)x", operands_))) for name, operands_ in kernels)
    assert len(shapes) == 12 and len(kernels) == 16, shapes  # 2 + 6 a length; the forward's two are both rules'


def _mover_calls(k, tokens, hidden, one):
    """The three row movers alone, jitted, and their operands at a block of
    `tokens` x `k` assignments of `hidden` bf16."""
    from galvatron_tpu.ops import moe

    rows, words = k * tokens, hidden // 256
    shaped = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    return {
        "moe_rows_pack": (lambda x: moe._pack_rows(x, moe.PACK_TILE), shaped((rows, hidden), jnp.bfloat16)),
        "moe_rows_back": (lambda packed, inv, w: moe._rows_back(packed, inv, w, tokens, hidden, jnp.bfloat16,
                                                                 moe.ROWS_BACK_TILE),
                          shaped((rows * words, 128), jnp.uint32), shaped((rows,), jnp.int32),
                          shaped((tokens, k), jnp.float32)),
        "moe_rows_out": (lambda *operands: moe._rows_out(*operands, moe.ROWS_OUT_TILE), shaped((tokens * words, 128), jnp.uint32), shaped((rows,), jnp.int32),
                         shaped((rows, hidden), jnp.bfloat16), shaped((rows,), jnp.float32)),
    }


def test_the_row_movers_compile_at_the_largest_block_they_take_on_v5e(v5e_2x2):
    """`ops/moe.rows_form` has upper bounds, and they are what Mosaic was
    seen to take: every assignment's index is prefetched into SMEM (1 MiB on
    a v5e), so at `ROWS_MAX_ASSIGNMENTS` x `ROWS_MAX_HIDDEN` the three kernels
    compile, and a block a third longer (32768 tokens x 8: all of SMEM) is
    refused BY THE COMPILER, which is why `rows_form` hands it to XLA, as the
    parent did, before it gets there."""
    from galvatron_tpu.ops import moe

    one, bf16 = SingleDeviceSharding(v5e_2x2[0]), jnp.bfloat16
    k, hidden = 8, moe.ROWS_MAX_HIDDEN
    tokens = moe.ROWS_MAX_ASSIGNMENTS // k
    assert moe.rows_form(True, bf16, hidden, tokens, k) == "kernel"
    for name, (fn, *operands) in _mover_calls(k, tokens, hidden, one).items():
        assert "tpu_custom_call" in jax.jit(fn).lower(*operands).compile().as_text(), name
    longer = 32768
    assert moe.rows_form(True, bf16, hidden, longer, k) == "xla"
    assert moe.rows_form(True, bf16, 2 * hidden, tokens, k) == "xla"
    fn, *operands = _mover_calls(k, longer, hidden, one)["moe_rows_back"]
    with pytest.raises(Exception, match="smem"):
        jax.jit(fn).lower(*operands).compile()


def _count_instructions(hlo):
    return len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", hlo, re.M))


_DELTA_RULE_INSTRUCTIONS = {}  # impl -> its optimised module's: the kernel case reads the XLA case's


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_delta_rule_keeps_a_state_a_chunk_and_runs_on_the_mxu_on_v5e(v5e_2x2, impl):
    """The gated delta rule's core at the Qwen3-Next cell's widths (8192
    tokens, 16 key heads serving 32 value heads, 128 x 128 states), forward
    and backward, for a described v5e: no array of tokens x heads x d_k x d_v
    is ever formed (the recurrence token by token would keep one for its
    backward): the largest is the chunks' starting states. The XLA form: 64
    tokens a chunk; the chunks' products are matmuls and the state is carried
    by a loop. The kernel form (what the chip takes): the custom calls are
    there by their names, no loop and no matmul is left to XLA, and the
    optimised module holds under a tenth of the XLA form's instructions."""
    from galvatron_tpu.ops import linear_attention as L

    tokens, hk, hv, dk, dv = 8192, 16, 32, 128, 128
    chip = SingleDeviceSharding(v5e_2x2[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731
    operands = (sds((1, tokens, hk, dk), jnp.bfloat16), sds((1, tokens, hk, dk), jnp.bfloat16),
                sds((1, tokens, hv, dv), jnp.bfloat16), sds((1, tokens, hv), jnp.float32),
                sds((1, tokens, hv), jnp.float32))

    def compiled_with(form):
        def loss(*ops):
            o, state = L.gated_delta_rule(*ops, impl=form)
            return jnp.sum(o.astype(jnp.float32)) + jnp.max(jnp.abs(state))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*operands).compile()

    compiled = compiled_with(impl)
    hlo = compiled.as_text()
    instructions = _DELTA_RULE_INSTRUCTIONS[impl] = _count_instructions(hlo)
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"(?:bf16|f32)\[([0-9,]+)\]", hlo)]
    chunk = L.CHUNK if impl == "xla" else L.TILE
    assert max(sizes) == tokens // chunk * hv * dk * dv  # the kept chunk-start states
    assert max(sizes) * chunk == tokens * hv * dk * dv
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 1.0 * 2**30  # all heads at once: 2.3 GiB
    dots = len(re.findall(r" (?:dot|convolution)\(", hlo))
    if impl == "xla":
        assert " while(" in hlo and dots >= 20 and "tpu_custom_call" not in hlo
        return
    assert hlo.count("tpu_custom_call") == 2 and " while(" not in hlo and dots == 0
    for name in ("gdn_fwd", "gdn_bwd"):  # what a trace's op table will show
        assert len(re.findall(r'op_name="[^"]*%s' % name, hlo)) >= 1, name
    assert instructions * 10 < (_DELTA_RULE_INSTRUCTIONS.get("xla")
                                or _count_instructions(compiled_with("xla").as_text()))


def _calls(text, kernel, scope):
    """Custom calls of `kernel` whose op carries `scope` right above the
    kernel's name (or above the jit its caller is traced once under)."""
    return len(re.findall(r'custom-call\(.*op_name="[^"]*%s/(?:jit\([^)]*\)/)?%s[/"]' % (re.escape(scope), kernel), text))


@pytest.fixture(scope="module")
def qwen3_next_linear_layer(v5e_2x2):
    """One linear layer's mixer of the Qwen3-Next cell (8192 tokens, hidden
    2048, 16 key heads serving 32 value heads of 128, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip:
    -> (the optimised module's text, the forms its parts took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.linear import linear_mixer
    from galvatron_tpu.models.qwen3_next import qwen3_next_config
    from galvatron_tpu.ops import linear_attention as L

    tokens = 8192
    cfg = qwen3_next_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(cfg.layer_kinds()[0])
    chip = SingleDeviceSharding(v5e_2x2[0])
    # a mesh of the one described chip says where the operands lie (the
    # default backend here is the CPU)
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"linear": shapes["linear"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def loss(p, y):
        mixer = jax.checkpoint(lambda p, y: linear_mixer(p, y, None, lcfg, attn_sharding=where))
        out, _, counters = mixer(p, y)
        return jnp.sum(out.astype(jnp.float32)) + counters["state_abs_max"]

    before = dict(L.TOOK)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, {name: count - before.get(name, 0) for name, count in L.TOOK.items()
                  if count - before.get(name, 0)}


def test_the_linear_layers_surround_is_lane_aligned_passes_on_v5e(qwen3_next_linear_layer):
    """Between the two projections and the core a linear layer runs as
    Pallas passes over (tokens, channels) arrays, a head a block of 128
    lanes (ops/linear_attention.py): the four kernels are there under
    `gt.attn.linear` by their names, the core's two still under
    `gt.attn.delta`, and what the XLA form cost on a TPU's 8 x 128 tiling
    (PERF.md, PR 38) is gone from that scope: no view of the activations by
    (tokens, heads, 128) at all, so no norm's scale broadcast to full size,
    no physical reshape or relayout copy of a float32 (tokens, 4096) or
    (tokens, 2048) array; no slice of the projection's output written out
    and no padded parts of its cotangent summed."""
    from galvatron_tpu.obs import tracing

    text, took = qwen3_next_linear_layer
    assert took == {"conv_norm_pallas": 1, "gated_norm_pallas": 1, "pallas": 1}
    tokens, keys = 8192, 2048

    calls = functools.partial(_calls, text)
    # a call each for q, k and v; the forward and its recomputation are one here (no scan between them)
    assert calls("conv_norm_fwd", tracing.ATTN_LINEAR) == 3 and calls("conv_norm_bwd", tracing.ATTN_LINEAR) == 3
    assert calls("gated_norm_fwd", tracing.ATTN_LINEAR) == 1 and calls("gated_norm_bwd", tracing.ATTN_LINEAR) == 1
    assert calls("gdn_fwd", tracing.ATTN_DELTA) == 1 and calls("gdn_bwd", tracing.ATTN_DELTA) == 1
    assert text.count("tpu_custom_call") == 10
    for kernel in ("conv_norm", "gated_norm"):  # never under the core's scope, whose roofline reads it alone
        assert not calls(kernel + "_fwd", tracing.ATTN_DELTA) and not calls(kernel + "_bwd", tracing.ATTN_DELTA)
    assert not re.search(r"\[(?:1,)?%d,(?:32|16),128\]" % tokens, text)  # no view by heads
    offenders = []
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or tracing.ATTN_LINEAR not in line:
            continue
        name, result, kind = found.groups()
        # the result's arrays over all tokens, at least (tokens, 2048) large: activations, not weights
        over_tokens = {dtype for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]*)\]", result)
                       if str(tokens) in dims.split(",")
                       and np.prod([int(d) for d in dims.split(",")]) >= tokens * keys}
        if (("f32" in over_tokens and kind in ("reshape", "copy", "transpose", "broadcast"))
                or (over_tokens and kind in ("slice", "dynamic-slice", "pad", "concatenate"))):
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)


@pytest.fixture(scope="module")
def kimi_kda_layer(v5e_2x2):
    """One Kimi-Delta-Attention mixer at the Kimi-Linear cell's widths (8192
    tokens, hidden 2304, 32 heads of 128, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip:
    -> (the optimised module's text, the forms its core and its passes took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.kimi_linear import kimi_linear_config
    from galvatron_tpu.models.parts.kda import kda_mixer
    from galvatron_tpu.ops import linear_attention as L

    tokens = 8192
    cfg = kimi_linear_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("kda")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"kda": shapes["kda"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def loss(p, y):
        mixer = jax.checkpoint(lambda p, y: kda_mixer(p, y, None, lcfg, attn_sharding=where))
        out, _, counters = mixer(p, y)
        return jnp.sum(out.astype(jnp.float32)) + counters["state_abs_max"]

    before = dict(L.TOOK)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, {name: count - before.get(name, 0) for name, count in L.TOOK.items()
                  if count - before.get(name, 0)}


def test_the_kda_layers_core_is_two_kernels_once_each_on_v5e(kimi_kda_layer):
    """The per-channel rule's core on a TPU: `kda_fwd` and `kda_bwd` under
    `gt.attn.kda_rule`, ONCE each under the layer's `jax.checkpoint` (the
    rule keeps its own residuals: the backward does not run the forward again;
    the first forward and the recomputation are one here, no scan between
    them), nothing of them under the surround's scope, which its own readers
    read, no other kernel under the core's (its roofline divides a fixed cost
    by all that scope holds), and no view of the activations by (tokens, 32,
    128) under it: a head is a block of 128 lanes of a (tokens, 4096) array."""
    from galvatron_tpu.obs import tracing

    text, took = kimi_kda_layer
    assert took["kda_pallas"] == 1 and "kda_xla" not in took
    tokens = 8192
    assert _calls(text, "kda_fwd", tracing.ATTN_KDA_RULE) == 1 and _calls(text, "kda_bwd", tracing.ATTN_KDA_RULE) == 1
    assert len(re.findall(r'custom-call\(.*op_name="[^"]*%s/' % re.escape(tracing.ATTN_KDA_RULE), text)) == 2
    assert not _calls(text, "kda_fwd", tracing.ATTN_KDA) and not _calls(text, "kda_bwd", tracing.ATTN_KDA)
    for line in text.splitlines():
        if tracing.ATTN_KDA_RULE in line:
            assert not re.search(r"\[(?:1,)?%d,32,128\]" % tokens, line), line[:200]


def test_the_kda_layers_surround_is_lane_aligned_passes_on_v5e(kimi_kda_layer):
    """Between its projections and the core a Kimi-Delta-Attention layer runs
    as Pallas passes over (tokens, channels) arrays, a head a block of 128
    lanes (ops/linear_attention.py: the linear layers' kernels under another
    `Layout`, and the per-channel gate's pair): every pass is there under
    `gt.attn.kda_mixer` by its name and none under `gt.attn.kda_rule`; no view
    of an activation by (tokens, 32, 128) is left ANYWHERE in the module; and
    under the mixer's scope no float32 (tokens, 4096) or (tokens, 12288) array
    is reshaped, copied, transposed or broadcast and no slice, pad or
    concatenation of an activation is written out."""
    from galvatron_tpu.obs import tracing

    text, took = kimi_kda_layer
    assert took == {"kda_pallas": 1, "kda_conv_norm_pallas": 1, "kda_gate_pallas": 1, "kda_gated_norm_pallas": 1}
    tokens, smallest = 8192, 2048
    passes = {"conv_norm_fwd": 3, "conv_norm_bwd": 3, "kda_gate_fwd": 1, "kda_gate_bwd": 1,
              "gated_norm_fwd": 1, "gated_norm_bwd": 1}  # a call each for q, k and v; forward and recomputation are one here
    for kernel, count in passes.items():
        assert _calls(text, kernel, tracing.ATTN_KDA) == count, kernel
        assert not _calls(text, kernel, tracing.ATTN_KDA_RULE), kernel
    assert text.count("tpu_custom_call") == 2 + sum(passes.values())
    assert not re.search(r"\[(?:1,)?%d,32,128\]" % tokens, text)  # no view by heads
    offenders = []
    for line in text.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or tracing.ATTN_KDA not in line:
            continue
        name, result, kind = found.groups()
        over_tokens = {dtype for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]*)\]", result)
                       if str(tokens) in dims.split(",")
                       and np.prod([int(d) for d in dims.split(",")]) >= tokens * smallest}
        if (("f32" in over_tokens and kind in ("reshape", "copy", "transpose", "broadcast"))
                or (over_tokens and kind in ("slice", "dynamic-slice", "pad", "concatenate"))):
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)


@pytest.fixture(scope="module")
def phi4_mamba_layer(v5e_2x2):
    """One Mamba-1 mixer at the Phi-4-mini-flash cell's widths (8192 tokens,
    hidden 2560, 5120 channels, states of 16, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip with
    the scan in each form: -> {form: (the optimised module's text, its
    temporaries in bytes, what `selective_scan.TOOK` gained)}."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import mamba
    from galvatron_tpu.models.phi4flash import phi4flash_config
    from galvatron_tpu.ops import selective_scan as SS

    tokens = 8192
    cfg = phi4flash_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("mamba1")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"mamba": shapes["mamba"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def compiled(where):
        def loss(p, y):
            mixer = jax.checkpoint(lambda p, y: mamba.mamba_mixer(p, y, None, lcfg, attn_sharding=where))
            out, _, counters = mixer(p, y)
            return jnp.sum(out.astype(jnp.float32)) + counters["selscan_state_abs_max"]

        before = collections.Counter(SS.TOOK)
        step = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile()
        return step.as_text(), step.memory_analysis().temp_size_in_bytes, dict(SS.TOOK - before)

    # with no sharding the call reads the default backend, the CPU's here: the XLA form for the same chip
    return {"pallas": compiled(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))),
            "xla": compiled(None)}


def test_the_mamba_layers_scan_keeps_a_chunks_state_on_the_chip_on_v5e(phi4_mamba_layer):
    """The selective scan on a TPU: `selscan_fwd` and `selscan_bwd` under
    `gt.attn.selscan`, once each under the layer's `jax.checkpoint` (the rule
    keeps its own residuals; the first forward and the recomputation are one
    here, no scan between them), no other kernel, none of them under the
    mixer's own scope `gt.attn.mamba`, and no loop left whose carry is every
    chunk's state, which the XLA form for the same chip has; the layer's
    temporaries are no more than that form's."""
    from galvatron_tpu.obs import tracing

    text, temp, took = phi4_mamba_layer["pallas"]
    xla_text, xla_temp, xla_took = phi4_mamba_layer["xla"]
    assert took == {"pallas": 1} and xla_took == {"xla": 1}
    for kernel in ("selscan_fwd", "selscan_bwd"):
        assert _calls(text, kernel, tracing.ATTN_SELSCAN) == 1, kernel
        assert not _calls(text, kernel, tracing.ATTN_MAMBA), kernel
    assert text.count("tpu_custom_call") == 2 and "tpu_custom_call" not in xla_text
    every_chunks_state = r"f32\[1,64,16,5120\]"
    carried = [line for line in xla_text.splitlines() if re.search(r"\bwhile\(", line) and re.search(every_chunks_state, line)]
    assert carried  # the form this PR takes off the chip's path
    assert not [line for line in text.splitlines() if re.search(r"\bwhile\(", line) and re.search(every_chunks_state, line)]
    assert temp <= xla_temp, (temp, xla_temp)


# ------------------------------------------------- the window kernels (Laguna)
FLASH_PATTERNS = (r"^flash_attention[.:]", r"^flash_mha_bwd_dkv", r"^flash_mha_bwd_dq")  # benchmarks/layer_metrics/flash_ms.py


def _window_loss(sharding):
    def loss(q, k, v):
        with jax.named_scope("gt.layers.r1"):  # as in the step: the kernels' calls lie inside a run's scope
            out = A.core_attention(q, k, v, window=512, sharding=sharding)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


def _custom_calls(text):
    """The names of a compiled program's Mosaic calls, as the trace labels them."""
    return sorted(line.split("=")[0].strip().lstrip("%") for line in text.splitlines()
                  if "custom_call_target=\"tpu_custom_call\"" in line)


@pytest.mark.parametrize("tokens", [8192, 16384])
def test_the_window_kernels_compile_at_the_cells_shapes_for_v5e(v5e_2x2, tokens):
    """64 query heads on 8 KV heads of 128 under a window of 512, the Laguna
    cell's window layers (and at twice their tokens, scripts/laguna_chip_check.py's
    16384), through `impl="auto"`: two Mosaic calls, forward and backward, whose
    names NONE of `flash_ms`'s three patterns match (or `flash_roofline` would
    price a band as a causal triangle), each on ONE line of the compiled text
    with its `op_name` (the benchmark's trace reader reads an instruction's
    first line: a kernel with `metadata=`, as jax's splash kernels, loses its
    scope there), k and v at their own 8 heads, and under a tenth of the
    temporaries a repeat of k and v to 64 heads would take."""
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, tokens, 64, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, tokens, 8, 128), jnp.bfloat16, sharding=one)
    before = collections.Counter(A.TOOK)
    fn = jax.grad(_window_loss(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))), argnums=(0, 1, 2))
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    names = _custom_calls(text)
    assert [n.rsplit(".", 1)[0] for n in names] == ["window_attn_bwd", "window_attn_fwd"]
    assert not any(re.search(rx, name) for rx in FLASH_PATTERNS for name in names)
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" in line:
            assert "gt.layers.r1" in re.search(r'op_name="([^"]*)"', line).group(1)
    assert A.TOOK - before == {"window_pallas": 1}
    # q, its cotangent's float32 square, the transposes and the backward's float32 shares of dk and dv
    # (4 x 32 MiB at 8192): no 64-head copy of k or v (2 x 128 MiB at 8192)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30 * tokens / 8192


@pytest.mark.parametrize("as_projected", [False, True], ids=["q_turned_before", "as_projected"])
def test_the_window_kernels_run_in_a_manual_region_on_a_dp4_mesh(v5e_2x2, as_projected):
    """Four sequences over four chips (dp with ZeRO runs the family): each chip
    its own row through the kernels, its rows of the rotation's tables and of
    the gate logits with it where the call brings them, no collective."""
    from galvatron_tpu.ops.rope import half_split_tables

    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "m0"))
    sh = NamedSharding(mesh, P("m0", None, None, None))
    q = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16, sharding=sh)
    kv = jax.ShapeDtypeStruct((4, 2048, 4, 128), jnp.bfloat16, sharding=sh)
    logits = jax.ShapeDtypeStruct((4, 2048, 16), jnp.bfloat16, sharding=NamedSharding(mesh, P("m0", None, None)))
    where = A.KernelSharding(mesh, ("m0",), ())

    def loss(q, k, v, logits):
        if not as_projected:
            return _window_loss(where)(q, k, v)
        positions = jnp.broadcast_to(jnp.arange(2048), (4, 2048))
        out = A.core_attention(q, k, v, window=512, sharding=where, q_rope=half_split_tables(positions, 128),
                               head_gate=logits)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(q, kv, kv, logits).compile().as_text()
    assert len(_custom_calls(text)) == 2
    for collective in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert collective not in text, collective


@pytest.fixture(scope="module")
def laguna_window_layer(v5e_2x2):
    """One window layer's mixer of the Laguna cell (8192 tokens, hidden 2048,
    64 query heads on 8 KV heads of 128, a window of 512, the gate a head,
    bf16) under the cell's recomputation, forward and backward, compiled for one
    described chip: -> (the optimised module's text, the forms its call took)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.laguna import laguna_config
    from galvatron_tpu.models.parts.window import window_mixer

    tokens = 8192
    cfg = laguna_config(num_layers=5, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("window")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    where = A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({name: shapes[name] for name in ("wq", "wkv", "wo", "wg")},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16),
                             jax.ShapeDtypeStruct((1, tokens), jnp.int32)))

    def loss(p, y, positions):
        mixer = jax.checkpoint(lambda p, y: window_mixer(p, y, positions, lcfg, mesh=None, axes=None, attn_bias=None,
                                                         attn_sharding=where, return_kv=False))
        return jnp.sum(mixer(p, y)[0].astype(jnp.float32))

    before = collections.Counter(A.TOOK)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile().as_text()
    return text, dict(A.TOOK - before)


def test_the_window_layer_reads_q_where_the_projection_wrote_it_on_v5e(laguna_window_layer):
    """A window layer on a TPU (PR 50): Mosaic compiles both kernels in the
    as-projected form, `window_attn_fwd` and `window_attn_bwd` once each under
    `gt.attn.band` (rope's tables and the gate logits among their operands), and
    nothing else of the layer makes a pass over a q-sized array: NO array by
    heads ((8192, 64, 128) or (64, 8192, 128), any dtype) is left anywhere in
    the module, and every (8192, 64 x 128) result of an instruction is a
    kernel's or a matmul's own (a fusion around a convolution): no transpose,
    no copy, no elementwise pass between the q projection and the kernel, the
    kernel and `wo`, `wo`'s backward and the kernel, the kernel and the
    projection's backward."""
    from galvatron_tpu.obs import tracing

    text, took = laguna_window_layer
    assert took == {"window_pallas": 1, "window_as_projected": 1}
    assert _calls(text, "window_attn_fwd", tracing.ATTN_WINDOW_BAND) == 1
    assert _calls(text, "window_attn_bwd", tracing.ATTN_WINDOW_BAND) == 1
    assert text.count("tpu_custom_call") == 2
    tokens, width = 8192, 64 * 128
    assert not re.search(r"\[(?:1,)?(?:%d,64|64,%d),128\]" % (tokens, tokens), text)  # no view by heads
    entry = text[text.index("\nENTRY"):]
    q_sized = r"(?:bf16|f32)\[(?:1,)?%d,%d\]" % (tokens, width)
    offenders, matmuls = [], 0
    for line in entry.splitlines():
        found = re.match(r"\s+(?:ROOT )?(\S+) = (.*?[})]) ([a-z\-]+)\(", line)
        if not found or not re.search(q_sized, found.group(2)):
            continue
        name, result, kind = found.groups()
        if kind in ("get-tuple-element", "bitcast", "parameter") or "tpu_custom_call" in line:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        body = text[text.index("\n" + called.group(1) + " "):].split("\n}\n", 1)[0] if called else ""
        if kind == "fusion" and " convolution(" in body:
            matmuls += 1
        else:
            offenders.append("%s = %s %s" % (name, result[:80], kind))
    assert not offenders, "\n".join(offenders)
    assert matmuls == 2  # the q projection (recomputed: the first forward is the same program here) and wo's backward
    # the kernels take the flat projection's result and wo's cotangent as they lie, and dq goes to the matmuls so
    for kernel, operand in (("window_attn_fwd", "convolution"), ("window_attn_bwd", "convolution")):
        call = next(line for line in entry.splitlines() if "tpu_custom_call" in line and kernel in line.split("=")[0])
        assert re.search(r"custom-call\(%" + operand, call), call[:200]

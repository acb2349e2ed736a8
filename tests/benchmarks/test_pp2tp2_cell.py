"""The pipeline's cell, `qwen7-c4-pp2tp2` (pp2 x tp2, GPipe, 4 microbatches):
the benchmark's own unstacking of a pipelined model's `stages` against the
program's, the plain reference on the pipelined model's weights through the
harness on four virtual devices at a tiny size, the manifest's side, the flash
readers' call shape under `--chunks`, and `pp_padding_ms` on labels recorded
from the cell's compiled step with times a hand can add."""

import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmarks import cells, harness, scopes, stages

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
CELL = "qwen7-c4-pp2tp2"
FOUR_CHIP = ("qwen7-c4-tp2dp2", CELL)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
# the cell's files with every size made small; the switches, the reference
# and the checks are the files' own
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 512, "max_position_embeddings": 64}
TINY_TRAFFIC = {"global_batch": 8, "seq_length": 32}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def roofline():
    return cells.load_module(REPO, "benchmarks/layer_metrics/flash_roofline.py")


# ------------------------------------------------------------ the unstacking
def test_offsets_and_an_uneven_division_by_hand():
    """Three layers over two stages as (2, 1): slot 0 holds layers 0 and 2,
    slot 1 layer 1 and the short stage's zeros, which no layer reads."""
    assert stages.offsets((2, 1)) == [0, 2] and stages.offsets((2, 2, 2)) == [0, 2, 4]
    slots = [{"w": np.array([[0.0, 0.5], [2.0, 2.5]])}, {"w": np.array([[1.0, 1.5], [0.0, 0.0]])}]
    tree = stages.per_layer_tree({"embed": {"wte": 7}, "stages": slots}, (2, 1))
    assert sorted(tree) == ["embed", "layers"] and tree["embed"] == {"wte": 7}
    assert [layer["w"].tolist() for layer in tree["layers"]] == [[0.0, 0.5], [1.0, 1.5], [2.0, 2.5]]
    # a per-layer tree (pp = 1) comes back as it is
    plain = {"embed": 1, "layers": [2, 3]}
    assert stages.per_layer_tree(plain, (2,)) == plain
    with pytest.raises(ValueError, match="do not hold a division"):
        stages.per_layer_tree({"stages": slots}, (1, 1))
    with pytest.raises(ValueError, match="do not hold a division"):
        stages.per_layer_tree({"stages": slots}, (2, 1, 1))


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from galvatron_tpu import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.llama import llama_config
    from galvatron_tpu.runtime import construct_hybrid_parallel_model

    cfg = llama_config("llama-0.3b", num_layers=4, hidden_size=64, ffn_hidden=128, num_heads=4,
                       num_kv_heads=2, head_dim=16, vocab_size=512, qkv_bias=True,
                       compute_dtype=jnp.float32)
    hp = HybridParallelConfig(world_size=4, pp=2, layers=[LayerStrategy(tp=2, checkpoint=1)] * 4,
                              global_bsz=8, chunks=4, vocab_tp=2)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:4])
    return cfg, hp, model, model.init_params(jax.random.PRNGKey(5))


def test_the_unstacking_gives_the_programs_per_layer_tree_leaf_for_leaf(tiny_model):
    """The oracle is the program's own way back (`parallel/pipeline.unstack_params`),
    which the benchmark does not import outside this test."""
    import jax

    from galvatron_tpu.parallel import pipeline

    _, hp, _, params = tiny_model
    assert "stages" in params and "layers" not in params
    ours = stages.per_layer_tree(params, tuple(hp.pp_division))
    theirs = pipeline.unstack_params(params["stages"], hp)
    assert len(ours["layers"]) == len(theirs) == 4
    for mine, yours in zip(ours["layers"], theirs):
        assert jax.tree.structure(mine) == jax.tree.structure(yours)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(yours)):
            assert a.shape == b.shape and np.array_equal(np.asarray(a), np.asarray(b))
    # four different layers, not one four times
    kernels = [np.asarray(layer["wo"]["kernel"]) for layer in ours["layers"]]
    assert all(not np.array_equal(kernels[0], k) for k in kernels[1:])
    for key in ("embed", "final_norm", "lm_head"):
        assert ours[key] is params[key]


def test_the_reference_on_the_unstacked_tree_is_the_pipelined_loss_in_float32(tiny_model):
    """Float32 compute on both sides: the GPipe loss over 4 microbatches on a
    pp2 x tp2 mesh against the plain reference on the same weights through
    the benchmark's unstacking, to float32 rounding."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    cfg, hp, model, params = tiny_model
    tokens = jax.random.randint(jax.random.PRNGKey(6), (8, 32), 0, 512)
    batch = model.shard_batch(dict(
        tokens=tokens, positions=jnp.broadcast_to(jnp.arange(32), (8, 32)),
        labels=jnp.roll(tokens, -1, 1), loss_mask=jnp.ones((8, 32), jnp.float32)))
    pipelined = float(jax.jit(model.loss_fn)(params, batch))
    ref = cells.load_module(REPO, "benchmarks/references/decoder_lm.py")
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    with pytest.raises(ValueError, match="per-layer tree"):
        ref.loss(params, batch, fields)  # the stacked tree is still refused by name
    division = tuple(hp.pp_division)
    plain = float(jax.jit(lambda p, b: ref.loss(stages.per_layer_tree(p, division), b, fields))(
        params, batch))
    assert pipelined == pytest.approx(plain, abs=2e-5)
    # and a reference that read the stages in the wrong order would not pass
    swapped = float(jax.jit(lambda p, b: ref.loss(
        {**stages.per_layer_tree(p, division),
         "layers": stages.per_layer_tree(p, division)["layers"][::-1]}, b, fields))(params, batch))
    assert abs(swapped - pipelined) > 1e-4


# ------------------------------------------------- through the harness, tiny
@pytest.fixture()
def tiny_root(tmp_path):
    """A checkout's worth of benchmark whose pipeline cell is small enough for
    the CPU: the repo's files, the cell's configuration and mix with the sizes
    overwritten and `--world_size 4` (the CPU has eight virtual devices)."""
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    cell = cells.load_cell(REPO, CELL)
    config = {**cell.config, **TINY}
    config["program"] = {**config["program"], "preset": "llama-0.3b",
                         "fields": {**config["program"]["fields"], "head_dim": 16}}
    traffic = {**cell.traffic, **TINY_TRAFFIC,
               "train_flags": ["--world_size", "4"] + cell.traffic["train_flags"]}
    (tmp_path / "benchmarks/configs/qwen2.5-7b-d4.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b8-s2k-pp2tp2.json").write_text(json.dumps(traffic))
    return str(tmp_path)


def test_the_cell_runs_through_the_harness_and_its_reference_reads_the_stages(tiny_root, tmp_path):
    cell = cells.load_cell(tiny_root, CELL)
    assert cell.traffic["train_flags"][2:] == cells.load_cell(REPO, CELL).traffic["train_flags"]
    lines = []
    out = tmp_path / "out"
    out.mkdir()
    result = harness.run_cell(cell, seed=2**31 + 53, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(out), say=lambda **o: lines.append(o))
    detail = lines[-1]
    # everything but the TPU kernel holds on the CPU: one compilation, none in
    # the window, the parameters on all four devices, the pipeline's sends and
    # the tp sums in the step, and the reference within the cell's own 2e-3
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert detail["checks"]["params_span_all_chips"] and detail["checks"]["layout_collectives"]
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}
    assert result["attempted"] == detail["window"]["steps"] >= 1 and result["failed"] == 0


# ------------------------------------------------------- the manifest's side
def test_the_mix_and_the_entry_load_and_say_what_the_layout_is():
    cell = cells.load_cell(REPO, CELL)
    assert (cell.chips, cell.tokens_a_step, cell.workload["config"]) == (4, 8 * 2048, "qwen2.5-7b-d4")
    assert cell.workload["traffic"] == "b8-s2k-pp2tp2" and cell.traffic["warmup_steps"] == 6
    assert cell.traffic["train_flags"] == ["--pp_deg", "2", "--global_tp_deg", "2", "--chunks", "4",
                                           "--vocab_tp", "2", "--checkpoint", "1"]
    # no dp axis: no reduce-scatter is promised, the stage sends are
    assert cell.collectives == ("all-reduce", "collective-permute")
    assert roofline().layout(cell) == {"tp": 2, "pp": 2, "cp": 1, "dp": 1, "chunks": 4}
    assert len(cell.workload["why"]) <= 200 and "20 %" in cell.workload["why"]
    # the configuration is the accepted one, shared with the other four-chip cell
    assert cells.load_cell(REPO, "qwen7-c4-tp2dp2").config == cell.config


def test_the_cell_lists_pp_padding_ms_and_no_other_cell_does():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    entry = [m for m in manifest["per_layer"] if m["name"] == "pp_padding_ms"]
    assert len(entry) == 1 and entry[0] == {
        "name": "pp_padding_ms", "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "layouts: parallel/pipeline.py", "moves": "tokens_per_s_chip", "workloads": [CELL]}
    for workload in manifest["workloads"]:
        names = {m["name"] for m in cells.load_cell(REPO, workload["name"]).metrics("per_layer")}
        assert ("pp_padding_ms" in names) == (workload["name"] == CELL)
        # the collectives' readers are the four-chip cells', the ZeRO-2 copy's the dp cell's
        assert ("collective_ms" in names) == ("collective_exposed_ms" in names) == (
            workload["name"] in FOUR_CHIP)
        assert ("param_gather_ms" in names) == (workload["name"] == "qwen7-c4-tp2dp2")
        assert workload["chips"] == (4 if workload["name"] in FOUR_CHIP else 1)
    names = {m["name"] for m in cells.load_cell(REPO, CELL).metrics("per_layer")}
    assert {"mlp_ms", "mlp_remat_ms", "mlp_roofline", "attn_proj_ms", "flash_ms", "flash_roofline",
            "layers_fwd_ms", "layers_rest_ms", "head_loss_ms", "embed_ms", "unscoped_pct"} <= names
    assert [m["name"] for m in cells.load_cell(REPO, CELL).metrics("end_to_end")] == [
        "tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"]
    # at most a quarter of the cells, rounded down, may take four chips
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


# ------------------------------------------------- the flash readers' shapes
def parents_kernel_shapes(cell):
    """`flash_roofline.kernel_shapes` as it stood before it knew `--chunks`."""
    flags = [str(f) for f in cell.traffic["train_flags"]]

    def flag(name):
        return int(flags[flags.index(name) + 1]) if name in flags else 1

    tp = flag("--global_tp_deg")
    dp = cell.chips // (tp * flag("--pp_deg") * flag("--global_cp_deg"))
    f = cell.fields
    return (cell.traffic["global_batch"] // dp, f["num_heads"] // tp, cell.traffic["seq_length"],
            f["head_dim"])


def test_a_call_has_two_rows_here_and_the_parents_shapes_in_the_other_twelve():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    shapes = {}
    for workload in manifest["workloads"]:
        cell = cells.load_cell(REPO, workload["name"])
        shapes[cell.name] = roofline().kernel_shapes({"cell": cell})
        if cell.name != CELL:
            assert "--chunks" not in cell.traffic["train_flags"]
            assert shapes[cell.name] == parents_kernel_shapes(cell), cell.name
    # a tick's microbatch: 8 rows / 4 chunks, 28 heads / tp 2
    assert shapes[CELL] == (2, 14, 2048, 128) and parents_kernel_shapes(
        cells.load_cell(REPO, CELL))[0] == 8
    assert shapes["qwen7-c4-tp2dp2"] == (4, 14, 2048, 128)
    assert shapes["qwen7-c1-s2k"] == (4, 28, 2048, 128) and shapes["qwen7-c1-s8k"] == (1, 28, 8192, 128)
    assert len(shapes) >= 13


def test_mlp_roofline_counts_a_stages_layers_on_all_its_microbatches():
    """Under pp2 x tp2 a device multiplies half the columns of 2 of the 4
    layers for all 8 x 2048 tokens: the same FLOPs as under tp2 x dp2 (4
    layers, the replica's 4 x 2048); the padding tick is in the time alone."""
    ops = {"fusion.1:jvp_/while/body/closed_call/vmap_gt.mlp_/dot_general": [100e-3, 5.0]}
    by_cell = {}
    for name in FOUR_CHIP:
        run = {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, name)}
        by_cell[name] = read("mlp_roofline", run)
    flops = 3 * 8192 * 4 * (3 * 2 * 3584 * 18944 // 2)
    assert by_cell[CELL] == pytest.approx(100 * flops / 197e12 / 100e-3, rel=1e-12)
    assert by_cell[CELL] == by_cell["qwen7-c4-tp2dp2"]


# ----------------------------------------- pp_padding_ms on recorded labels
@pytest.fixture(scope="module")
def labels():
    with open(os.path.join(FIXTURES, "qwen7-c4-pp2tp2-labels.json")) as f:
        return json.load(f)


def run_of(ops, cell=CELL):
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, cell)}


def test_pp_padding_ms_is_a_fifth_of_the_tick_scans_time(labels):
    """Labels as the cell's compiled step carries them, times by hand: inside
    the tick scan 40 + 20 + 10 + 5 + 5 + 10 + 4 + 1 + 3 + 2 = 100 ms; the
    embedding, the head, the update and an unscoped copy outside it."""
    run = run_of(labels["ops_a_step"])
    inside = labels["by_hand"]["inside_tick_scan_ms"]
    assert inside == 100.0 and scopes.ms_a_step(run, scopes.TICK_BODY) == pytest.approx(inside)
    # pp 2, chunks 4: one tick in five holds padding
    assert read("pp_padding_ms", run) == pytest.approx(inside * 1 / 5) == pytest.approx(
        labels["by_hand"]["pp_padding_ms"])
    # the nested scopes read through the vmap's wrapper (`vmap_gt.mlp_/`) and without it
    assert read("mlp_ms", run) == pytest.approx(labels["by_hand"]["mlp_ms"])
    assert read("mlp_remat_ms", run) == pytest.approx(labels["by_hand"]["mlp_remat_ms"])
    assert read("attn_proj_ms", run) == pytest.approx(labels["by_hand"]["attn_proj_ms"])
    assert read("flash_ms", run) == pytest.approx(labels["by_hand"]["flash_ms"])
    # `gt.layers.r<k>` is `run_layers`' scope, which the pipeline does not call
    assert [read("layers_%s_ms" % phase, run) for phase in ("fwd", "remat", "bwd")] == [0.0] * 3
    assert read("layers_rest_ms", run) == 0.0
    assert read("head_loss_ms", run) == pytest.approx(labels["by_hand"]["head_loss_ms"])
    # what carries no `gt.` scope: the kernels, norms and sends inside the
    # scan, and the copy outside
    assert read("unscoped_pct", run) == pytest.approx(labels["by_hand"]["unscoped_pct"])


def test_flash_roofline_prices_the_padding_ticks_calls_as_run(labels):
    """5 ticks x 2 layers: 20 forward calls (10 recomputed), 10 dkv, 10 dq, on
    2 rows and 14 heads each."""
    run = run_of(labels["ops_a_step"])
    from benchmarks import flops

    least = sum(calls * flops.flash_kernel_cost(kind, 2, 14, 2048, 128)["flops"] / 197e12
                for kind, calls in (("fwd", 20), ("dkv", 10), ("dq", 10)))
    took = labels["by_hand"]["flash_ms"] / 1e3
    assert read("flash_roofline", run) == pytest.approx(100 * least / took, rel=1e-12)
    assert 0 < read("flash_roofline", run) < 100


@pytest.mark.parametrize("ops,why", [
    ({"fusion.1:jvp_gt.layers.r0_/while/body/closed_call/gt.mlp/dot_general": [50e-3, 1.0],
      "fusion.2:jvp_gt.head_loss_/dot_general": [9e-3, 1.0]}, "a_layer_runs_scan_is_no_tick_scan"),
    ({"fusion.1:jvp_/while/body/dot_general": [1e-3, 1.0]}, "a_program_without_scopes"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_nothing_to_read_leaves_pp_padding_ms_out(ops, why):
    assert read("pp_padding_ms", run_of(ops)) is None
    assert read("pp_padding_ms", {"trace": None, "cell": cells.load_cell(REPO, CELL)}) is None


def test_a_mix_without_a_pipeline_has_no_padding(labels):
    run = run_of(labels["ops_a_step"], cell="qwen7-c4-tp2dp2")
    assert read("pp_padding_ms", run) is None


# ------------------------------------------------- the step recorded on the chip
@pytest.fixture(scope="module")
def recorded():
    from benchmarks import trace

    reduced = trace.reduce(trace.load_events(
        os.path.join(FIXTURES, CELL + ".trace_events.json.gz")), harness.STEP_NAMES)
    with open(os.path.join(FIXTURES, CELL + ".expected.json")) as f:
        return {"trace": reduced, "peak": PEAK, "cell": cells.load_cell(REPO, CELL)}, json.load(f)


def test_the_readers_on_the_step_recorded_on_the_chip(recorded):
    """Device 0's events of the traced tail of one `--trace 2` run of the cell
    on four v5e chips; the expected numbers are what that run reported."""
    run, expected = recorded
    assert run["trace"]["steps"] == expected["steps"] == 5
    for name, value in expected.items():
        if name not in ("recorded", "steps", "busy_s_device_0"):
            assert read(name, run) == pytest.approx(value, rel=1e-9), name
    assert 0 < read("flash_roofline", run) < 100 and 0 < read("mlp_roofline", run) < 100


def test_a_stage_runs_its_layers_on_every_tick_the_padding_tick_too(recorded):
    """What `pp_padding_ms` rests on, read off the chip: each of device 0's
    kernels, two layers' forward, recomputed forward, dkv and dq, is called
    `chunks + pp - 1` = 5 times a step, not 4, and the device is busy all the
    step: a stage computes on zeros in the bubble, it does not idle."""
    run, expected = recorded
    kernels = {label: calls for label, (_, calls) in run["trace"]["ops_a_step"].items()
               if label.startswith("flash_")}
    assert len(kernels) == 8 and set(kernels.values()) == {5.0}
    assert all(re.search(scopes.TICK_BODY, label) for label in kernels)
    assert run["trace"]["busy_s"] / run["trace"]["window_s"] > 0.999
    inside = scopes.ms_a_step(run, scopes.TICK_BODY)
    assert read("pp_padding_ms", run) == pytest.approx(inside / 5)
    # the body is the layers' parts, the kernels, and what carries no scope there
    parts = read("mlp_ms", run) + read("attn_proj_ms", run) + read("flash_ms", run)
    assert 0.85 * inside < parts < inside
    # outside it: the embedding, the head and the update, and little else
    outside = sum(read(n, run) for n in ("head_loss_ms", "embed_ms", "optimizer_ms", "guard_select_ms"))
    step_ms = 1e3 * run["trace"]["busy_s"] / run["trace"]["steps"]
    assert 0.96 * step_ms < inside + outside < step_ms

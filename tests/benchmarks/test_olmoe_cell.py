"""The OLMoE cell: its configuration from its own files through the harness
on the CPU at a tiny size, its readers on handmade labels and events, and its
FLOPs and grouped-matmul costs by hand arithmetic."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "olmoe-c1-s4k"
READERS = ("moe_ms", "moe_experts_ms", "moe_dispatch_ms", "moe_gmm_roofline",
           "expert_load_max_over_mean")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference,
# the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 2, "num_experts": 8,
        "num_experts_per_tok": 2, "vocab_size": 512, "max_position_embeddings": 32}
# 0.01 x 2 (load balancing at 2 of 8) + 0.001 x (ln 8 + 64 x 0.02^2 / 2)^2
TINY_PLUS = 0.01 * 2 + 0.001 * (math.log(8) + 0.0128) ** 2
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/olmoe.py")


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_five_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names) and "flash_roofline" in names
    assert "collective_ms" not in names
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs)
    assert cell.chips == 1 and cell.tokens_a_step == 8192
    assert cell.config["reduced"].keys() == {"num_hidden_layers"}


def test_every_width_is_the_published_one():
    """The catalog's row for OLMoE-1B-7B-0125-Instruct, key for key; the
    depth alone is cut."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in published.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and config["num_hidden_layers"] == 1
    from galvatron_tpu.models import olmoe

    assert config["source"] == olmoe.OLMOE_1B_7B_SOURCE
    preset = olmoe.PUBLISHED["olmoe-1b-7b"]
    assert all(preset[k] == v for k, v in published.items() if k in preset)


def test_the_first_loss_carries_the_router_losses():
    cell = cells.load_cell(REPO, CELL)
    plus = cell.config["checks"]["first_loss"]["plus"]
    assert harness.expected_first_loss(cell) == pytest.approx(
        math.log(50304) + 2048 * 0.02 ** 2 / 2 + plus, abs=1e-12)
    # what a router whose inputs were independent would add: 0.01 x 8 + 0.001 x
    # E[logsumexp^2] of 64 logits of variance 0.82. The measured constant lies
    # above it by what uneven routing adds to the load balancing (0.01 x 0.87)
    mean = math.log(64) + 0.8192 / 2 - (math.exp(0.8192) - 1) / 128
    independent = 0.01 * 8 + 0.001 * (mean ** 2 + (math.exp(0.8192) - 1) / 64)
    assert independent == pytest.approx(0.1008, abs=3e-4)
    assert independent < plus < independent + 0.02


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f = cell.fields
    layer = costs().layer_fwd_flops_a_token(f, 4096)
    by_hand = {
        "projections": 4 * 2 * 2048 * 2048,  # q, k, v, out: 33.6 M
        "scores": 2 * 2 * 4096 * 2048 // 2,  # q k^T and p v, the causal half: 16.8 M
        "experts": 8 * 3 * 2 * 2048 * 1024,  # 8 of 64, gate up down: 100.7 M
        "router": 2 * 2048 * 64,  # 0.26 M
    }
    assert layer == sum(by_hand.values())
    assert [round(v / 1e6, 1) for v in by_hand.values()] == [33.6, 16.8, 100.7, 0.3]
    head = 2 * 2048 * 50304
    assert cells.flops_a_token(cell) == 3 * (layer + head) == costs().train_flops_a_token(f, 4096)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(1.072, abs=5e-4)
    assert head / (layer + head) == pytest.approx(0.58, abs=0.005)  # 8 % at 16 layers
    assert head / (16 * layer + head) == pytest.approx(0.08, abs=0.005)


def test_grouped_matmul_cost_by_hand():
    f = cells.load_cell(REPO, CELL).fields
    rows = 8192 * 8  # exact under dropless dispatch
    assert costs().gmm_dims(f, "in") == (2048, 2048) and costs().gmm_dims(f, "out") == (1024, 2048)
    into = costs().gmm_cost(f, "in", 8192)
    assert into["flops"] == 2 * rows * 2048 * 2048
    # 64 kernels of 2048 x 2048 once, the rows in and out, bf16
    assert into["bytes"] == 2 * (64 * 2048 * 2048 + rows * 2048 + rows * 2048)
    out = costs().gmm_cost(f, "out", 8192)
    assert out["flops"] == 2 * rows * 1024 * 2048 == into["flops"] / 2
    assert out["bytes"] == 2 * (64 * 1024 * 2048 + rows * 1024 + rows * 2048)
    from benchmarks import flops

    assert flops.least_time_s(into, PEAK) == (into["flops"] / 197e12, "compute")
    assert flops.least_time_s(out, PEAK)[1] == "compute"


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(dense=False):
    """A routed step's ops as the compiled step labels them: the program's
    scope names, nested, under the transforms' wrappers."""
    r0 = tracing.layers_scope(0)
    fwd = "jit(train_step)/jvp(%s)/" % r0
    bwd = "jit(train_step)/transpose(jvp(%s))/checkpoint/" % r0
    remat = bwd + "rematted_computation/"
    experts_in = "%s/%s/" % (tracing.MOE_EXPERTS, tracing.MOE_GMM_IN)
    experts_out = "%s/%s/" % (tracing.MOE_EXPERTS, tracing.MOE_GMM_OUT)
    ops = {
        label("fusion.1", fwd + "dot_general"): [3e-3, 1],  # attention's projections
        label("fusion.2", bwd + "dot_general"): [6e-3, 1],
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
    }
    if not dense:
        ops.update({
            label("fusion.3", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.2e-3, 1],
            label("sort.4", fwd + tracing.MOE_DISPATCH + "/jit(argsort)/sort"): [0.3e-3, 1],
            label("gather.5", remat + tracing.MOE_DISPATCH + "/gather"): [0.5e-3, 1],
            label("convert.1", fwd + experts_in + "convert_element_type"): [0.01e-3, 1],
            label("gmm.1", fwd + experts_in + "jit(gmm)/pallas_call"): [4e-3, 1],
            label("gmm.2", remat + experts_in + "jit(gmm)/pallas_call"): [4e-3, 1],
            label("gmm.3", bwd + experts_in + "jit(gmm)/pallas_call"): [4e-3, 1],
            label("tgmm.1", bwd + experts_in + "jit(tgmm)/pallas_call"): [4e-3, 1],
            label("gmm.4", fwd + experts_out + "jit(gmm)/pallas_call"): [2e-3, 1],
            label("gmm.5", remat + experts_out + "jit(gmm)/pallas_call"): [2e-3, 1],
            label("gmm.6", bwd + experts_out + "jit(gmm)/pallas_call"): [2e-3, 1],
            label("tgmm.2", bwd + experts_out + "jit(tgmm)/pallas_call"): [2e-3, 1],
            label("fusion.7", fwd + tracing.MOE_EXPERTS + "/jit(silu)/mul"): [0.49e-3, 1],
            label("fusion.8", bwd + tracing.MOE_COMBINE + "/gather"): [1e-3, 1],
        })
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": [], "window_steps": (0, 0)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("moe_experts_ms", run) == pytest.approx(24.5)
    assert read("moe_dispatch_ms", run) == pytest.approx(2.0)
    assert read("moe_ms", run) == pytest.approx(
        read("moe_experts_ms", run) + read("moe_dispatch_ms", run))
    # nested inside gt.layers.r<k>: forward, recomputation and backward keep
    # adding up to everything under the layers' scopes
    parts = [cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % n).read(run)
             for n in ("layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms")]
    assert sum(parts) == pytest.approx(scopes.ms_a_step(run, scopes.LAYERS)) == pytest.approx(35.5)
    # 4 calls a kind; least time 4 x 2.79 + 4 x 1.395 ms of the 24 ms they took
    into, out = costs().gmm_cost(run["cell"].fields, "in", 8192), costs().gmm_cost(
        run["cell"].fields, "out", 8192)
    least = 4 * into["flops"] / 197e12 + 4 * out["flops"] / 197e12
    assert read("moe_gmm_roofline", run) == pytest.approx(100 * least / 24e-3)
    assert 0 < read("moe_gmm_roofline", run) < 100


def test_a_dense_step_and_a_program_without_scopes_give_nothing_to_read():
    """What the parent of this PR and the dense cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in (handmade(dense=True), {**handmade(), "trace": None}, {**handmade(), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("moe_gmm_roofline", dense_cell) is None  # its configuration names no `flops`


def test_the_expert_load_is_the_step_events_own_counter():
    run = handmade()
    assert "expert_load_max_over_mean" in telemetry.EXPERT_STEP_FIELDS
    run["events"] = [
        {"type": "step", "iter": i, "loss": 11.3, "expert_load_max_over_mean": v}
        for i, v in enumerate([1.5, 1.10, 1.14, 1.12, 9.0])
    ] + [{"type": "step", "iter": 2}, {"type": "log", "message": "x"}]
    run["window_steps"] = (1, 4)
    assert read("expert_load_max_over_mean", run) == pytest.approx(1.12)


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/olmoe-1b-7b-d1.json")
    config.update(TINY)
    config["reduced"]["num_hidden_layers"]["here"] = TINY["num_hidden_layers"]
    config["program"]["fields"]["head_dim"] = 16
    config["checks"]["first_loss"]["plus"] = TINY_PLUS
    (tmp_path / "benchmarks/configs/olmoe-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s32-olmoe.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 32, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "olmoe-tiny", "source": "test", "why": "test",
                                "reduced": ["num_hidden_layers"],
                                "file": "benchmarks/configs/olmoe-tiny.json"})
    manifest["workloads"].append({"name": "olmoe-tiny-cell", "config": "olmoe-tiny",
                                  "traffic": "b2-s32-olmoe", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("olmoe-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel
    check holds on the CPU."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "olmoe-tiny-cell")
    test_manifest.check_the_program_receives_the_published_keys(root, "olmoe-tiny-cell")
    cell = cells.load_cell(root, "olmoe-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 27, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    # the objective's three terms, against the plain reference's
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(
        math.log(512) + 64 * 0.02 ** 2 / 2 + TINY_PLUS, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 32)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

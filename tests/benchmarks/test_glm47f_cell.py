"""The GLM-4.7-Flash cell: its configuration from its own files through the
harness on the CPU at a tiny size, its readers on handmade labels and events,
and its FLOPs and grouped-matmul costs by hand arithmetic."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm47f-c1-s8k"
READERS = ("latent_attn_ms", "moe_held_ms", "moe_shared_ms", "mtp_ms",
           "moe_rows_held_over_even", "moe_held_gmm_roofline",
           # the routed half split by scope, and the load beside the rows (REVIEW, PR 32)
           "moe_held_experts_ms", "moe_held_dispatch_ms", "moe_held_load_max_over_mean")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference,
# the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
        "v_head_dim": 16, "n_routed_experts": 2, "router_width": 8, "experts_held_start": 4,
        "num_experts_per_tok": 2, "vocab_size": 512, "max_position_embeddings": 32}
TINY_PLUS = 0.3 * (math.log(512) + 64 * 0.02 ** 2 / 2)
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/glm4_moe_lite.py")


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_nine_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names) and {"flash_ms", "flash_roofline"} <= set(names)
    assert not {"collective_ms", "moe_ms", "moe_gmm_roofline", "param_gather_ms"} & set(names)
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs)
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
            assert metric["layer"] in ("model: models/base.py", "kernels: ops/moe.py")
    assert cell.chips == 1 and cell.tokens_a_step == 8192 and cell.workload["traffic"] == "b1-s8k-lrw2k"
    assert cell.config["reduced"].keys() == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    # no count of cells: at most a quarter of them, rounded down, take four chips
    assert 1 <= sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(manifest["workloads"]) // 4)


def test_every_width_is_the_published_one():
    """The catalog's row for GLM-4.7-Flash, key for key; the depth, the
    experts held and the vocabulary alone are cut, to the guide's floors."""
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in published.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        5, 8, 154880 // 8)
    for key, cut in config["reduced"].items():
        assert cut["published"] == published[key] and cut["here"] == config[key]
    # the router's width is a stated key of its own, and the program gets all three
    fields = cells.config_fields(config)
    assert config["router_width"] == 64 == fields["num_experts"]
    assert (fields["experts_held"], fields["experts_held_start"], fields["experts_per_token"]) == (8, 0, 4)
    assert (fields["num_heads"], fields["head_dim"]) == (20, 256)  # what flash_roofline reads
    # the guide's floors: four routed layers after the dense one, 8 experts, an eighth
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= published["vocab_size"]
    from galvatron_tpu.models import glm4_moe_lite

    assert config["source"] == glm4_moe_lite.GLM_47_FLASH_SOURCE
    preset = glm4_moe_lite.PUBLISHED["glm-4.7-flash"]
    assert all(preset[k] == v for k, v in published.items() if k in preset)
    assert config["router_bias_update_rate"] == glm4_moe_lite.ROUTER_BIAS_UPDATE_RATE
    assert config["mtp_loss_weight"] == glm4_moe_lite.MTP_LOSS_WEIGHT


def test_the_first_loss_carries_the_mtp_term():
    cell = cells.load_cell(REPO, CELL)
    plus = cell.config["checks"]["first_loss"]["plus"]
    cross_entropy = math.log(19360) + 2048 * 0.02 ** 2 / 2
    assert plus == pytest.approx(0.3 * cross_entropy, abs=1e-12)
    assert harness.expected_first_loss(cell) == pytest.approx(1.3 * cross_entropy, abs=1e-12)


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    attention = c.attention_fwd_flops_a_token(f, 8192)
    assert attention["projections"] == 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                                            + 512 * 20 * 448 + 5120 * 2048)
    assert attention["core"] == 2 * 8192 * 20 * (256 + 256) // 2  # q k^T and p v, the causal half
    dense = 3 * 2 * 2048 * 10240
    shared = 3 * 2 * 2048 * 1536
    routed_here = shared * 4 * 8 / 64  # the even share of a token's 4 experts held here
    router = 2 * 2048 * 64
    attn = sum(attention.values())
    assert c.block_fwd_flops_a_token(f, 8192, False) == attn + dense
    assert c.block_fwd_flops_a_token(f, 8192, True) == attn + shared + routed_here + router
    head, weh = 2 * 2048 * 19360, 2 * 4096 * 2048
    fwd = (attn + dense) + 4 * (attn + shared + routed_here + router) + head \
        + (head + weh + attn + shared + routed_here + router)
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(3.625, abs=5e-4)
    # ISSUE 32's shares of the forward FLOPs (and the cell's `why`)
    shares = {"latent attention": 6 * attn, "core": 6 * attention["core"], "head twice": 2 * head,
              "dense layer": dense, "shared experts": 5 * shared, "routed rows held": 5 * routed_here}
    assert {k: round(100 * v / fwd) for k, v in shares.items()} == {
        "latent attention": 63, "core": 42, "head twice": 13, "dense layer": 10,
        "shared experts": 8, "routed rows held": 4}
    assert c.routed_blocks(f) == 5


def test_grouped_matmul_cost_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    assert c.gmm_dims(f, "in") == (2048, 3072) and c.gmm_dims(f, "out") == (1536, 2048)
    rows = 8192 * 4 * 8 // 64  # the even share a block: 4096
    into = c.gmm_cost(f, "in", rows)
    assert into["flops"] == 2 * rows * 2048 * 3072
    # ONE kernel (the counter does not say which held groups are empty, and an
    # empty group's kernel is never read), the rows in and out, bf16
    assert into["bytes"] == 2 * (2048 * 3072 + rows * 2048 + rows * 3072)
    out = c.gmm_cost(f, "out", rows)
    assert out["flops"] == into["flops"] / 2
    assert out["bytes"] == 2 * (1536 * 2048 + rows * 1536 + rows * 2048)
    assert c.gmm_cost(f, "in", 0.0) == {"flops": 0.0, "bytes": 0.0}  # a block that sent it nothing
    assert flops.least_time_s(into, PEAK)[1] == "compute"
    assert flops.least_time_s(c.gmm_cost(f, "in", 256), PEAK)[1] == "memory"  # few rows: the kernels' bytes


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(rows_a_step=5 * 4096.0, dense=False):
    """The new cell's step as the compiled step labels it: the program's
    scope names, nested, under the transforms' wrappers."""
    r0, r1 = tracing.layers_scope(0), tracing.layers_scope(1)
    fwd = "jit(train_step)/jvp(%s)/" % r1
    bwd = "jit(train_step)/transpose(jvp(%s))/checkpoint/" % r1
    remat = bwd + "rematted_computation/"
    mtp = "jit(train_step)/jvp(%s)/" % tracing.MTP
    mtp_bwd = "jit(train_step)/transpose(jvp(%s))/" % tracing.MTP
    experts_in = "%s/%s/" % (tracing.MOE_EXPERTS, tracing.MOE_GMM_IN)
    experts_out = "%s/%s/" % (tracing.MOE_EXPERTS, tracing.MOE_GMM_OUT)
    ops = {
        label("fusion.1", "jit(train_step)/jvp(%s)/dot_general" % r0): [3e-3, 1],  # the dense layer
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 2],
    }
    if not dense:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_LATENT + "/dot_general"): [2e-3, 4],
            label("fusion.3", bwd + tracing.ATTN_LATENT + "/dot_general"): [4e-3, 4],
            label("fusion.4", mtp + tracing.ATTN_LATENT + "/dot_general"): [0.5e-3, 1],
            label("flash_attention.7", fwd + "pallas_call"): [9e-3, 4],  # not latent's: flash_ms
            label("fusion.5", fwd + tracing.MOE_SHARED + "/dot_general"): [1e-3, 4],
            label("fusion.6", bwd + tracing.MOE_SHARED + "/dot_general"): [2e-3, 4],
            label("fusion.7", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.2e-3, 4],
            label("gather.8", remat + tracing.MOE_DISPATCH + "/gather"): [1.3e-3, 4],
            label("gather.9", mtp_bwd + tracing.MOE_COMBINE + "/gather"): [0.5e-3, 1],
            label("gmm.1", fwd + experts_in + "jit(gmm)/pallas_call"): [2e-3, 4],
            label("gmm.2", remat + experts_in + "jit(gmm)/pallas_call"): [2e-3, 4],
            label("gmm.3", bwd + experts_in + "jit(gmm)/pallas_call"): [2e-3, 4],
            label("tgmm.1", bwd + experts_in + "jit(tgmm)/pallas_call"): [2e-3, 4],
            label("gmm.4", mtp + experts_in + "jit(gmm)/pallas_call"): [0.5e-3, 1],
            label("gmm.5", fwd + experts_out + "jit(gmm)/pallas_call"): [1e-3, 4],
            label("tgmm.2", bwd + experts_out + "jit(tgmm)/pallas_call"): [1e-3, 4],
            label("fusion.9", mtp + "dot_general"): [0.7e-3, 1],  # Weh
        })
    events = [] if rows_a_step is None else [
        {"type": "step", "iter": i, "loss": 13.3, "expert_rows_held": rows_a_step,
         "expert_rows_held_over_even": rows_a_step / (5 * 4096.0),
         "expert_load_max_over_mean": 2.0 + i / 3} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("latent_attn_ms", run) == pytest.approx(6.5)  # layers' and MTP's, not the flash call
    assert read("moe_shared_ms", run) == pytest.approx(3.0)
    assert read("moe_held_ms", run) == pytest.approx(0.2 + 1.3 + 0.5 + 8.5 + 2.0)
    # the two add up to everything under gt.moe., the routed block's own reader's pattern
    assert read("moe_held_ms", run) + read("moe_shared_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.moe\."))
    # MTP: its latent attention, its experts, its gather and Weh; not its head pass
    assert read("mtp_ms", run) == pytest.approx(0.5 + 0.5 + 0.5 + 0.7)
    # the routed half by scope: the grouped matmuls' side follows the rows, the rest does not
    assert read("moe_held_experts_ms", run) == pytest.approx(8.5 + 2.0)
    assert read("moe_held_dispatch_ms", run) == pytest.approx(0.2 + 1.3 + 0.5)
    assert read("moe_held_experts_ms", run) + read("moe_held_dispatch_ms", run) == pytest.approx(
        read("moe_held_ms", run))
    assert read("moe_rows_held_over_even", run) == pytest.approx(1.0)
    assert read("moe_held_load_max_over_mean", run) == pytest.approx(2.5)
    assert "expert_rows_held_over_even" in telemetry.SHARE_STEP_FIELDS
    assert "expert_load_max_over_mean" in telemetry.EXPERT_STEP_FIELDS


def test_the_roofline_is_taken_at_the_rows_the_counter_reports():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    run = handmade()
    into, out = c.gmm_cost(f, "in", 4096), c.gmm_cost(f, "out", 4096)
    least = 17 * flops.least_time_s(into, PEAK)[0] + 8 * flops.least_time_s(out, PEAK)[0]
    assert read("moe_held_gmm_roofline", run) == pytest.approx(100 * least / 10.5e-3)
    # twice the rows in the same time: twice the share (compute bound), not the even share's
    assert read("moe_held_gmm_roofline", handmade(2 * 5 * 4096.0)) == pytest.approx(
        2 * read("moe_held_gmm_roofline", run), rel=1e-9)


@pytest.mark.parametrize("over_even", [0.05, 0.3, 1.0, 2.4, 8.0])
def test_kernels_that_run_at_their_least_time_read_100_whatever_the_rows(over_even):
    """A share over 100 % would be refused as an impossible reading. Fed the
    counter's rows, the reader cannot pass 100 for calls that took their own
    least time (8.0: every token sent to the held experts)."""
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    rows = over_even * 4096.0
    run = handmade(5 * rows)
    least = {kind: flops.least_time_s(c.gmm_cost(f, kind, rows), PEAK)[0] for kind in ("in", "out")}
    for lab, value in run["trace"]["ops_a_step"].items():
        if lab.startswith(("gmm", "tgmm")):
            value[0] = value[1] * least["in" if "gmm_in" in lab else "out"]
    assert read("moe_held_gmm_roofline", run) == pytest.approx(100.0)
    for value in run["trace"]["ops_a_step"].values():
        value[0] *= 1.25  # slower kernels read under 100
    assert read("moe_held_gmm_roofline", run) == pytest.approx(80.0)


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in (handmade(dense=True, rows_a_step=None), {**handmade(None), "trace": None},
                {**handmade(None), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    assert read("moe_held_gmm_roofline", handmade(None)) is None  # kernels, but no counter
    assert read("moe_held_gmm_roofline", {**handmade(), "events": None}) is None
    olmoe = {**handmade(), "cell": cells.load_cell(REPO, "olmoe-c1-s4k")}
    assert read("moe_held_gmm_roofline", olmoe) is None  # its FLOPs module counts no routed blocks
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("moe_held_gmm_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/glm-4.7-flash-d5-e8-v8.json")
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    config["program"]["fields"]["head_dim"] = 16
    config["checks"]["first_loss"]["plus"] = TINY_PLUS
    (tmp_path / "benchmarks/configs/glm-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s32-glm.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 32, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "glm-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/glm-tiny.json"})
    manifest["workloads"].append({"name": "glm-tiny-cell", "config": "glm-tiny",
                                  "traffic": "b2-s32-glm", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("glm-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel
    check holds on the CPU, a share of the experts (2 of 8, from the 5th)
    included."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "glm-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "glm-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "glm-tiny-cell")
    cell = cells.load_cell(root, "glm-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 32, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    # the objective's two terms, against the plain reference's
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(
        1.3 * (math.log(512) + 64 * 0.02 ** 2 / 2), abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 32)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

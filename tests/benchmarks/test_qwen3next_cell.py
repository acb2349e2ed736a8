"""The Qwen3-Next cell: its configuration from its own files through the
harness on the CPU at a tiny size, its readers on handmade labels and events,
and its FLOPs and the delta rule's floor by hand arithmetic."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "qwen3next-c1-s8k"
READERS = ("linear_attn_ms", "delta_rule_ms", "delta_rule_roofline", "linear_state_abs_max",
           "q3n_moe_held_dispatch_ms", "q3n_moe_held_experts_ms", "q3n_moe_shared_ms",
           "q3n_moe_rows_held_over_even")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference,
# the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 8, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "num_experts": 4, "router_width": 16, "experts_held_start": 8,
        "num_experts_per_tok": 4, "vocab_size": 512, "max_position_embeddings": 128}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/qwen3_next.py")


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_eight_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names) and {"flash_ms", "flash_roofline"} <= set(names)
    assert not {"collective_ms", "moe_ms", "moe_held_ms", "latent_attn_ms", "mtp_ms",
                "param_gather_ms"} & set(names)
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs)
    # the entries of those names, in that order, wherever they stand (new entries go last)
    assert [m["name"] for m in manifest["per_layer"] if m["name"] in READERS] == list(READERS)
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
            assert metric["layer"] in ("model: models/base.py", "kernels: ops/moe.py",
                                       "kernels: ops/linear_attention.py")
    assert cell.chips == 1 and cell.tokens_a_step == 8192
    assert cell.workload["traffic"] == "b1-s8k-lrw2k"
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    assert cell.traffic["warmup_steps"] == 6
    assert cell.config["reduced"].keys() == {"num_hidden_layers", "num_experts", "vocab_size"}
    # no count of cells: at most a quarter of them, rounded down, take four chips
    assert 1 <= sum(w["chips"] == 4 for w in manifest["workloads"]) <= max(1, len(manifest["workloads"]) // 4)


def test_every_width_is_the_published_one():
    """The catalog's row for Qwen3-Next-80B-A3B-Instruct, key for key; the
    depth, the experts held and the vocabulary alone are cut, to the guide's
    floors."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in published.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (
        4, 32, 151936 // 8)
    for key, cut in config["reduced"].items():
        assert cut["published"] == published[key] and cut["here"] == config[key]
    fields = cells.config_fields(config)
    assert config["router_width"] == 512 == fields["num_experts"]
    assert (fields["experts_held"], fields["experts_held_start"], fields["experts_per_token"]) == (32, 0, 10)
    # what flash_roofline reads is the ATTENTION layer's; the linear heads have names of their own
    assert (fields["num_heads"], fields["num_kv_heads"], fields["head_dim"]) == (16, 2, 256)
    assert (fields["linear_num_key_heads"], fields["linear_num_value_heads"]) == (16, 32)
    # the guide's floors: a whole period and four layers, 8 experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] % config["full_attention_interval"] == 0
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    from galvatron_tpu.models import qwen3_next

    assert config["source"] == qwen3_next.QWEN3_NEXT_SOURCE
    preset = qwen3_next.PUBLISHED["qwen3-next-80b-a3b"]
    assert all(preset[k] == v for k, v in published.items() if k in preset)
    assert set(published) - set(preset) == {"model_type"}
    assert config["router_aux_loss_coef"] == qwen3_next.ROUTER_AUX_LOSS_COEF


def test_the_first_loss_carries_the_routers_term():
    cell = cells.load_cell(REPO, CELL)
    plus = cell.config["checks"]["first_loss"]["plus"]
    # 0.001 x E sum_e f_e P_e, which is 10 where load and probability are independent
    assert 0.0100 <= plus <= 0.0105
    assert harness.expected_first_loss(cell) == pytest.approx(
        math.log(18992) + 2048 * 0.02 ** 2 / 2 + plus, abs=1e-12)
    assert cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    linear = c.linear_mixer_fwd_flops_a_token(f)
    assert linear["projections"] == 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
    assert linear["core"] == 6 * 32 * 128 * 128  # three (d_k, d_v) products a value head
    attention = c.attention_mixer_fwd_flops_a_token(f, 8192)
    assert attention["projections"] == 2 * (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
    assert attention["core"] == 2 * 8192 * 16 * (256 + 256) // 2  # q k^T and p v, the causal half
    expert = 3 * 2 * 2048 * 512
    moe = expert * 10 * 32 / 512 + expert + 2 * 2048 * 512 + 2 * 2048
    assert c.moe_fwd_flops_a_token(f) == moe
    head = 2 * 2048 * 18992
    fwd = 3 * sum(linear.values()) + sum(attention.values()) + 4 * moe + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(1.381, abs=5e-4)
    # a change of chunk, or of sequence length, cannot move the linear layers' count
    assert c.linear_mixer_fwd_flops_a_token(f) == linear and c.linear_layers(f) == 3
    # ISSUE 35's shares of the forward FLOPs (and the cell's `why`)
    shares = {"linear mixers": 3 * sum(linear.values()), "with their MoE halves": 3 * (sum(linear.values()) + moe),
              "full layer": sum(attention.values()) + 0 * moe, "head": head}
    assert {k: round(100 * v / fwd) for k, v in shares.items()} == {
        "linear mixers": 46, "with their MoE halves": 54, "full layer": 26, "head": 17}


def test_the_delta_rules_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.gdn_cost(f, 8192, "fwd"), c.gdn_cost(f, 8192, "bwd")
    assert fwd["flops"] == 6 * 32 * 128 * 128 * 8192 and bwd["flops"] == 2 * fwd["flops"]
    qkv, o, gates = (2 * 16 * 128 + 32 * 128) * 2, 32 * 128 * 2, 2 * 32 * 4
    assert fwd["bytes"] == (qkv + gates + o) * 8192  # each operand in, the output out, once
    assert bwd["bytes"] == (qkv + gates + o + qkv + gates) * 8192  # those, do, and the five gradients
    # memory bound at the chip's peaks: 0.25 ms forward, 0.41 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["bytes"] / 819e9, "memory")
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(0.415, abs=1e-3)


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, linear=True):
    """The new cell's step as the compiled step labels it: the program's
    scope names, nested, under the transforms' wrappers."""
    r0, r1 = tracing.layers_scope(0), tracing.layers_scope(1)
    fwd = "jit(train_step)/jvp(%s)/while/body/" % r0
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/checkpoint/" % r0
    remat = bwd + "rematted_computation/"
    full = "jit(train_step)/jvp(%s)/" % r1
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", full + "pallas_call"): [9e-3, 1],  # the attention layer's: flash_ms
        label("fusion.5", full + tracing.MOE_SHARED + "/dot_general"): [1e-3, 1],
        label("fusion.6", bwd + tracing.MOE_SHARED + "/dot_general"): [2e-3, 3],
        label("fusion.7", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.2e-3, 3],
        label("gather.8", remat + tracing.MOE_DISPATCH + "/gather"): [1.3e-3, 3],
        label("gather.9", bwd + tracing.MOE_COMBINE + "/gather"): [0.5e-3, 3],
        label("gmm.1", fwd + tracing.MOE_EXPERTS + "/gmm_in/jit(gmm)/pallas_call"): [2e-3, 3],
        label("tgmm.1", bwd + tracing.MOE_EXPERTS + "/gmm_out/jit(tgmm)/pallas_call"): [1e-3, 3],
    }
    if linear:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_LINEAR + "/dot_general"): [2e-3, 3],
            label("fusion.3", bwd + tracing.ATTN_LINEAR + "/dot_general"): [4e-3, 3],
            label("fusion.4", fwd + tracing.ATTN_DELTA + "/while/body/dot_general"): [3e-3, 384],
            label("fusion.8", remat + tracing.ATTN_DELTA + "/while/body/dot_general"): [3e-3, 384],
            label("fusion.9", bwd + tracing.ATTN_DELTA + "/checkpoint/dot_general"): [6e-3, 384],
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 10.27, "expert_rows_held": 4 * 5120.0,
         "expert_rows_held_over_even": 1.0 + 0.01 * i, "expert_load_max_over_mean": 1.6,
         "linear_decay_mean": 0.9, "linear_state_abs_max": 2.0 + i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("linear_attn_ms", run) == pytest.approx(6.0)  # not the core, not the flash call
    assert read("delta_rule_ms", run) == pytest.approx(12.0)  # forward, recomputed, backward
    # the two scopes are disjoint and add up to the linear mixers
    assert read("linear_attn_ms", run) + read("delta_rule_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.(linear|delta)"))
    assert read("q3n_moe_shared_ms", run) == pytest.approx(3.0)
    assert read("q3n_moe_held_experts_ms", run) == pytest.approx(3.0)
    assert read("q3n_moe_held_dispatch_ms", run) == pytest.approx(0.2 + 1.3 + 0.5)
    assert read("q3n_moe_rows_held_over_even", run) == pytest.approx(1.015)
    assert read("linear_state_abs_max", run) == pytest.approx(3.5)
    assert set(telemetry.LINEAR_STEP_FIELDS) == {"linear_decay_mean", "linear_state_abs_max"}
    assert set(telemetry.LINEAR_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    # the layer readers still see the nested scopes as the layers' (forward / recomputed / backward)
    assert scopes.ms_a_step(run, scopes.LAYERS_REMAT) == pytest.approx(1.3 + 3.0)


def test_the_share_of_the_floor_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 3 * sum(flops.least_time_s(c.gdn_cost(f, 8192, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(1.990, abs=2e-3)
    run = handmade()
    assert read("delta_rule_roofline", run) == pytest.approx(100 * least / 12e-3)
    # any time the floor allows: one forward and one backward a layer at their least times read
    # 100, and a recomputed forward, which every run under --checkpoint 1 has, reads less
    for lab, value in run["trace"]["ops_a_step"].items():
        if "gt.attn.delta" in lab:
            which = "bwd" if "transpose" in lab and "rematted" not in lab else "fwd"
            value[0] = 3 * flops.least_time_s(c.gdn_cost(f, 8192, which), PEAK)[0]
    with_remat = read("delta_rule_roofline", run)
    assert 50.0 < with_remat < 100.0
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.delta" in k and "rematted" in k)}
    assert read("delta_rule_roofline", run) == pytest.approx(100.0)


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    routed_alone = handmade(counters=False, linear=False)  # a routed model with no linear layer
    for name in ("linear_attn_ms", "delta_rule_ms", "delta_rule_roofline", "linear_state_abs_max"):
        assert read(name, routed_alone) is None
    glm = {**handmade(), "cell": cells.load_cell(REPO, "glm47f-c1-s8k")}
    assert read("delta_rule_roofline", glm) is None  # its FLOPs module has no gdn_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("delta_rule_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/qwen3-next-80b-a3b-d4-e32-v8.json")
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    config["checks"]["first_loss"]["plus"] = 0.004  # 0.001 x 4 a token of 16 experts
    (tmp_path / "benchmarks/configs/q3n-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-q3n.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"],
        "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "q3n-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/q3n-tiny.json"})
    manifest["workloads"].append({"name": "q3n-tiny-cell", "config": "q3n-tiny",
                                  "traffic": "b2-s128-q3n", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("q3n-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel
    check holds on the CPU, a share of the experts (4 of 16, from the 9th)
    and two chunks of the delta rule included."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "q3n-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "q3n-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "q3n-tiny-cell")
    cell = cells.load_cell(root, "q3n-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 35, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(
        math.log(512) + 64 * 0.02 ** 2 / 2 + 0.004, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

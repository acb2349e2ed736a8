"""The Kimi-Linear cell: its configuration against the catalog's row, its files
through the harness on the CPU at a tiny size, its readers on handmade labels
and events, and its FLOPs and the per-channel rule's floor by hand arithmetic.
Every assertion is by NAME or by membership: none by a position in `per_layer`
or `workloads`, nor by their lengths, so that a later PR's appended entries
break nothing here."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimilin-c1-s8k"
CONFIG = "kimi-linear-48b-a3b-d5-e8-v8"
READERS = ("kda_mixer_ms", "kda_rule_ms", "kda_rule_roofline", "kda_state_abs_max", "kimi_latent_attn_ms",
           "kimi_mlp_ms", "kimi_moe_held_dispatch_ms", "kimi_moe_held_experts_ms", "kimi_moe_shared_ms",
           "kimi_moe_rows_held_over_even")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the pattern, the switches, the
# reference, the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 2, "num_key_value_heads": 2, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "attention_call_head_dim": 32, "num_hidden_layers": 5,
        "num_experts": 4, "router_width": 16, "num_experts_per_token": 4, "vocab_size": 512}
TINY_FIELDS = {"linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 16,
               "linear_value_head_dim": 16}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KDA_LAYERS = [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26]


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/kimi_linear.py")


def published():
    """The catalog's row for Kimi-Linear-48B-A3B-Instruct, as ISSUE 42 quotes
    it (typed here: the catalog lies outside the repository)."""
    return {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
                               "kda_layers": KDA_LAYERS, "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_ten_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names)
    # the listless readers read it unasked
    assert {"flash_ms", "flash_roofline", "layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms",
            "layers_rest_ms", "unscoped_pct", "head_loss_ms", "embed_ms", "optimizer_ms",
            "guard_select_ms"} <= set(names)
    assert not {"collective_ms", "moe_ms", "moe_held_ms", "latent_attn_ms", "mtp_ms", "param_gather_ms",
                "linear_attn_ms", "delta_rule_ms", "mlp_ms", "mlp_roofline", "ssd_ms"} & set(names)
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs), other["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    model, rule, moe = "model: models/base.py", "kernels: ops/linear_attention.py", "kernels: ops/moe.py"
    layers = {"kda_mixer_ms": model, "kda_rule_ms": rule, "kda_rule_roofline": rule, "kda_state_abs_max": rule,
              "kimi_latent_attn_ms": model, "kimi_mlp_ms": model, "kimi_moe_held_dispatch_ms": moe,
              "kimi_moe_held_experts_ms": moe, "kimi_moe_shared_ms": model, "kimi_moe_rows_held_over_even": moe}
    for name in READERS:
        metric = by_name[name]
        assert CELL in metric["workloads"] and metric["moves"] == "tokens_per_s_chip"
        assert metric["layer"] == layers[name]
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (by_name["kda_rule_roofline"]["unit"], by_name["kda_rule_roofline"]["better"]) == ("%", "higher")
    assert {by_name[n]["source"] for n in ("kda_state_abs_max", "kimi_moe_rows_held_over_even")} == {
        "program_counter"}
    # a layer this PR names is one the manifest already had
    assert set(layers.values()) <= {m["layer"] for m in manifest["per_layer"] if m["name"] not in READERS}
    assert cell.chips == 1 and cell.tokens_a_step == 8192
    assert cell.workload["traffic"] == "b1-s8k-lrw2k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(cell.workload["why"]) <= 200 and len(entry["why"]) <= 200


def test_every_width_is_the_published_one_and_reduced_is_depth_experts_and_vocabulary():
    """The catalog's row, key for key; the depth, the experts held and the
    vocabulary alone are cut, to the guide's floors, and the nested group stays
    whole: the program runs its lists' first five layers."""
    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 163840 // 8)
    assert config["router_width"] == want["num_experts"] and config["experts_held_start"] == 0
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"Kimi-Linear-48B-A3B-Instruct"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    # the program's fields are read off the nested group
    fields = cells.config_fields(config)
    group = config["linear_attn_config"]
    from galvatron_tpu.models import kimi_linear

    assert fields["layer_types"] == kimi_linear.layer_types_from_lists(
        group["kda_layers"], group["full_attn_layers"]) and len(fields["layer_types"]) == 27
    assert fields["layer_types"][:5] == ["kda", "kda", "kda", "attention", "kda"]
    assert fields["layer_types"][24:] == ["kda", "kda", "attention"]  # the short last period
    assert (fields["linear_num_key_heads"], fields["linear_num_value_heads"]) == (group["num_heads"],) * 2
    assert (fields["linear_key_head_dim"], fields["linear_value_head_dim"]) == (group["head_dim"],) * 2
    assert fields["linear_conv_kernel"] == group["short_conv_kernel_size"]
    # what flash_roofline reads: the width the ONE attention call runs at
    assert (fields["num_heads"], fields["head_dim"]) == (32, 256)
    assert (fields["qk_nope_head_dim"], fields["qk_rope_head_dim"], fields["v_head_dim"],
            fields["kv_lora_rank"]) == (128, 64, 128, 512) and "q_lora_rank" not in fields
    assert (fields["num_experts"], fields["experts_held"], fields["experts_per_token"],
            fields["num_shared_experts"], fields["first_dense_layers"]) == (256, 8, 8, 1, 1)
    assert (fields["ffn_hidden"], fields["dense_ffn_hidden"], fields["routed_scaling_factor"]) == (1024, 9216, 2.446)
    assert fields["position_type"] == "none" and fields["tie_embeddings"] is False
    # the guide's floors: the leading dense layer once and four that follow it (a whole
    # period: three KDA to one MLA), 8 routed experts, an eighth of the vocabulary
    assert fields["layer_types"][1:5].count("kda") == 3 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= want["vocab_size"]
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    assert {"initializer_range", "gate_init", "conv_init", "column_layout", "router_bias_update_rate",
            "biases", "attention_call_head_dim", "head_dim"} <= set(config["assumed"])
    assert "32 chips share every layer" in config["deployment"] and "thirty-second" in config["deployment"]
    assert config["source"] == kimi_linear.KIMI_LINEAR_SOURCE
    preset = kimi_linear.PUBLISHED["kimi-linear-48b-a3b"]
    assert {k: preset[k] for k in want} == want
    assert config["initializer_range"] == kimi_linear.INITIALIZER_RANGE
    assert config["router_bias_update_rate"] == kimi_linear.ROUTER_BIAS_UPDATE_RATE


def test_the_program_built_from_the_file_counts_602_434_432_parameters():
    """ISSUE 42's table, derived here by hand and counted off the program."""
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    kda = 3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4096 * 4 + 32 + 4096 + 128
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    expert = 3 * 2304 * 1024
    routed = 8 * expert + expert + 2304 * 256 + 256
    norms, dense = 2 * 2304, 3 * 2304 * 9216
    by_hand = (kda + dense + norms) + 3 * (kda + routed + norms) + (mla + routed + norms) + 2 * 20480 * 2304 + 2304
    assert (kda, mla, routed, by_hand) == (39_514_272, 29_114_880, 64_291_072, 602_434_432)
    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert cfg.layer_kinds() == ("kda.dense", "kda.routed", "kda.routed", "routed", "kda.routed")
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == by_hand
    assert count * 16 / 1e9 == pytest.approx(9.64, abs=0.01)  # GB of state, of a chip's 16
    assert count * 12 / 2 ** 30 == pytest.approx(6.733, abs=0.001)  # what `step_args_gib` reads


def test_the_first_loss_is_derived_and_has_no_plus():
    cell = cells.load_cell(REPO, CELL)
    first = cell.config["checks"]["first_loss"]
    assert "plus" not in first and "DERIVED" in first["why"]
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(20480) + 2304 * 0.02 ** 2 / 2, abs=1e-12)
    assert harness.expected_first_loss(cell) == pytest.approx(10.388, abs=5e-4)
    assert first["abs"] <= 0.1 and cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    kda = c.kda_mixer_fwd_flops_a_token(f)
    assert kda["projections"] == 2 * (2304 * 12288 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 4096 * 2304)
    assert kda["core"] == 6 * 32 * 128 * 128  # three (d_k, d_v) products a head
    mla = c.attention_mixer_fwd_flops_a_token(f, 8192)
    assert mla["projections"] == 2 * (2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304)
    assert mla["core"] == 2 * 8192 * 32 * (192 + 128) // 2  # q k^T at 192, p v at 128, the causal half
    dense, routed = c.mlp_fwd_flops_a_token(f, False), c.mlp_fwd_flops_a_token(f, True)
    expert = 3 * 2 * 2304 * 1024
    assert dense == 3 * 2 * 2304 * 9216
    assert routed == (8 * 8 / 256 + 1) * expert + 2 * 2304 * 256  # the even share, the shared one, the router
    head = 2 * 2304 * 20480
    fwd = 4 * sum(kda.values()) + sum(mla.values()) + dense + 4 * routed + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert fwd / 1e6 == pytest.approx(767.7, abs=0.05) and cells.flops_a_token(cell) / 1e9 == pytest.approx(2.303, abs=5e-4)
    assert c.kda_layers(f) == 4
    # a change of sequence length moves the attention layer's count alone
    assert c.train_flops_a_token(f, 4096) - c.train_flops_a_token(f, 8192) == -3 * mla["core"] / 2
    # the shares of the forward FLOPs (ISSUE 42's, and the cell's `why`)
    shares = {"KDA mixers": 4 * sum(kda.values()), "MLA": sum(mla.values()), "dense MLP": dense,
              "routed halves": 4 * routed, "head": head}
    assert {k: round(100 * v / fwd, 1) for k, v in shares.items()} == {
        "KDA mixers": 42.8, "MLA": 18.5, "dense MLP": 16.6, "routed halves": 9.8, "head": 12.3}


def test_the_rules_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.kda_cost(f, 8192, "fwd"), c.kda_cost(f, 8192, "bwd")
    assert fwd["flops"] == 6 * 32 * 128 * 128 * 8192 and bwd["flops"] == 2 * fwd["flops"]
    qkv, o, gates = 3 * 4096 * 2, 4096 * 2, 4096 * 4 + 32 * 4  # the gate a head AND channel, float32
    assert gates * 2 > qkv + o > gates  # it weighs half of q + k + v + o
    assert fwd["bytes"] == (qkv + gates + o) * 8192  # each operand in, the output out, once
    assert bwd["bytes"] == (qkv + gates + o + qkv + gates) * 8192  # those, do, and the gradients
    # memory bound at the chip's peaks: 0.493 ms forward, 0.904 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["bytes"] / 819e9, "memory")
    assert flops.least_time_s(fwd, PEAK)[0] * 1e3 == pytest.approx(0.4929, abs=1e-3)
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(0.9039, abs=1e-3)


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, kda=True):
    """The cell's step as the compiled step labels it: four runs (the dense
    layer, two KDA + experts scanned, the MLA layer, one more KDA + experts),
    the program's scope names nested under the transforms' wrappers."""
    r0, r1, r2, r3 = (tracing.layers_scope(k) for k in range(4))
    first = "jit(train_step)/jvp(%s)/" % r0
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r1
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r1
    remat = bwd + "rematted_computation/"
    full = "jit(train_step)/jvp(%s)/" % r2
    last = "jit(train_step)/transpose(jvp(%s))/checkpoint/" % r3
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", full + "pallas_call"): [2e-3, 1],  # the MLA layer's: flash_ms
        label("fusion.5", full + tracing.ATTN_LATENT + "/dot_general"): [1.5e-3, 1],
        label("fusion.6", first + tracing.MLP + "/dot_general"): [3e-3, 1],
        label("fusion.7", fwd + tracing.MOE_SHARED + "/dot_general"): [1e-3, 2],
        label("fusion.8", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.5e-3, 2],
        label("fusion.9", bwd + tracing.MOE_DISPATCH + "/gather"): [4e-3, 2],
        label("fusion.13", remat + tracing.MOE_COMBINE + "/gather"): [1.5e-3, 2],
        label("gmm.3", bwd + tracing.MOE_EXPERTS + "/" + tracing.MOE_GMM_IN + "/pallas_call"): [2.5e-3, 2],
        label("fusion.10", fwd + "mul"): [0.5e-3, 2],  # a run's self time
    }
    if kda:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_KDA + "/dot_general"): [4e-3, 2],
            label("fusion.3", last + tracing.ATTN_KDA + "/dot_general"): [8e-3, 1],
            label("fusion.4", fwd + tracing.ATTN_KDA_RULE + "/while/body/closed_call/checkpoint/dot_general"): [20e-3, 64],
            label("fusion.11", remat + tracing.ATTN_KDA_RULE + "/while/body/closed_call/dot_general"): [20e-3, 64],
            label("fusion.12", bwd + tracing.ATTN_KDA_RULE + "/while/body/closed_call/checkpoint/dot_general"): [50e-3, 64],
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 10.39, "linear_state_abs_max": 1.0 + i,
         "expert_rows_held_over_even": 0.9 + 0.1 * i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("kda_mixer_ms", run) == pytest.approx(12.0)  # not the rule
    assert read("kda_rule_ms", run) == pytest.approx(90.0)  # forward, recomputed, backward
    # neither name begins the other: the two are disjoint and add up to the KDA mixers
    assert read("kda_mixer_ms", run) + read("kda_rule_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.kda_"))
    assert read("kimi_latent_attn_ms", run) == pytest.approx(1.5) == read("latent_attn_ms", run)
    assert read("kimi_mlp_ms", run) == pytest.approx(3.0) == read("mlp_ms", run)
    assert read("kimi_moe_shared_ms", run) == pytest.approx(1.0)
    assert read("kimi_moe_held_dispatch_ms", run) == pytest.approx(0.5 + 4.0 + 1.5)
    assert read("kimi_moe_held_experts_ms", run) == pytest.approx(2.5)
    assert read("kda_state_abs_max", run) == pytest.approx(2.5)
    assert read("kimi_moe_rows_held_over_even", run) == pytest.approx(1.05)
    assert read("flash_ms", run) == pytest.approx(2.0)
    assert set(telemetry.LINEAR_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    assert (tracing.ATTN_KDA, tracing.ATTN_KDA_RULE) == ("gt.attn.kda_mixer", "gt.attn.kda_rule")
    # the layer readers see the nested scopes as the layers', and the parts add up
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert parts["rest"] == pytest.approx(0.5) and parts[tracing.ATTN_KDA_RULE] == pytest.approx(90.0)
    assert parts[tracing.ATTN_KDA] == pytest.approx(12.0) and parts["flash"] == pytest.approx(2.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))


def test_the_share_of_the_floor_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 4 * sum(flops.least_time_s(c.kda_cost(f, 8192, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(5.587, abs=5e-3)
    run = handmade()
    assert read("kda_rule_roofline", run) == pytest.approx(100 * least / 90e-3)
    # any time the floor allows: one forward and one backward a layer at their least times read
    # 100, and a recomputed forward, which every run under --checkpoint 1 has, reads less
    for lab, value in run["trace"]["ops_a_step"].items():
        if "gt.attn.kda_rule" in lab:
            which = "bwd" if "transpose" in lab and "rematted" not in lab else "fwd"
            value[0] = 4 * flops.least_time_s(c.kda_cost(f, 8192, which), PEAK)[0]
    assert 50.0 < read("kda_rule_roofline", run) < 100.0
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.kda_rule" in k and "rematted" in k)}
    assert read("kda_rule_roofline", run) == pytest.approx(100.0)


def test_a_program_without_the_scopes_or_the_counters_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    no_kda = handmade(counters=False, kda=False)  # a program with the other scopes and no KDA layer
    for name in ("kda_mixer_ms", "kda_rule_ms", "kda_rule_roofline", "kda_state_abs_max",
                 "kimi_moe_rows_held_over_even"):
        assert read(name, no_kda) is None
    assert read("kimi_mlp_ms", no_kda) == pytest.approx(3.0)
    q3n = {**handmade(), "cell": cells.load_cell(REPO, "qwen3next-c1-s8k")}
    assert read("kda_rule_roofline", q3n) is None  # its FLOPs module has no kda_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("kda_rule_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    config["program"]["fields"].update(TINY_FIELDS)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    (tmp_path / "benchmarks/configs/kimi-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-kimi.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"],
        "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "kimi-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/kimi-tiny.json"})
    manifest["workloads"].append({"name": "kimi-tiny-cell", "config": "kimi-tiny",
                                  "traffic": "b2-s128-kimi", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("kimi-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel
    check holds on the CPU: four runs of layers, two chunks of the rule a
    sequence, latent attention padded to 32, 4 of 16 experts held."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "kimi-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "kimi-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "kimi-tiny-cell")
    cell = cells.load_cell(root, "kimi-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 42, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

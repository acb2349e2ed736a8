"""The LFM2-MoE cell: its configuration against the catalog's row, its files
through the harness on the CPU at a tiny size, its readers on handmade labels
and events, and its FLOPs, the gate pass's floor and the grouped matmul's cost
by hand arithmetic. Every assertion is by NAME or by membership: none by a
position in `per_layer` or `workloads`, nor by their lengths, so that a later
PR's appended entries break nothing here."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "lfm2moe-c1-s8k"
CONFIG = "lfm2-8b-a1b-d5-e8-v4"
READERS = ("shortconv_proj_ms", "conv_gate_ms", "conv_gate_roofline", "lfm2_attn_proj_ms", "lfm2_mlp_ms",
           "lfm2_moe_held_dispatch_ms", "lfm2_moe_held_experts_ms", "lfm2_moe_rows_held_over_even",
           "lfm2_moe_held_gmm_roofline")
REDUCED = {"num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOKENS = 2 * 8192
# the published file with every size made small; the pattern, the switches, the
# reference, the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 5, "num_dense_layers": 1,
        "num_experts": 2, "router_width": 8, "num_experts_per_tok": 2, "vocab_size": 512}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ATTENDS = (2, 6, 10, 14, 18, 21)


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/lfm2_moe.py")


def published():
    """The catalog's row for LFM2-8B-A1B, as ISSUE 46 quotes it (typed here: the
    catalog lies outside the repository)."""
    return {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": ["full_attention" if i in ATTENDS else "conv" for i in range(24)],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


# ------------------------------------------------------- the manifest's side
def test_the_cell_finds_its_files():
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(REPO, CELL)
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(REPO, CONFIG)
    test_manifest.check_the_program_receives_the_published_keys(REPO, CELL)


def test_the_cell_reports_its_nine_metrics_and_no_accepted_cell_does():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names
    # the listless readers read it unasked
    assert {"flash_ms", "flash_roofline", "layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms",
            "layers_rest_ms", "unscoped_pct", "head_loss_ms", "embed_ms", "optimizer_ms",
            "guard_select_ms", "device_idle_pct"} <= names
    assert not {"collective_ms", "moe_ms", "moe_held_ms", "latent_attn_ms", "mtp_ms", "param_gather_ms",
                "linear_attn_ms", "delta_rule_ms", "kda_rule_ms", "mlp_ms", "mlp_roofline", "attn_proj_ms",
                "ssd_ms", "moe_shared_ms", "kimi_moe_shared_ms"} & names
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = {m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")}
            assert not set(READERS) & theirs, other["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    model, moe = "model: models/base.py", "kernels: ops/moe.py"
    layers = {"shortconv_proj_ms": model, "conv_gate_ms": model, "conv_gate_roofline": model,
              "lfm2_attn_proj_ms": model, "lfm2_mlp_ms": model, "lfm2_moe_held_dispatch_ms": moe,
              "lfm2_moe_held_experts_ms": moe, "lfm2_moe_rows_held_over_even": moe,
              "lfm2_moe_held_gmm_roofline": moe}
    for name in READERS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
        assert metric["layer"] == layers[name]
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in ("conv_gate_roofline", "lfm2_moe_held_gmm_roofline"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == ("%", "higher")
    assert by_name["lfm2_moe_rows_held_over_even"]["source"] == "program_counter"
    # a layer this PR names is one the manifest already had
    assert set(layers.values()) <= {m["layer"] for m in manifest["per_layer"] if m["name"] not in READERS}
    assert cell.chips == 1 and cell.tokens_a_step == TOKENS
    assert cell.workload["traffic"] == "b2-s8k-lrw2k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    assert (cell.traffic["global_batch"], cell.traffic["seq_length"], cell.traffic["warmup_steps"]) == (2, 8192, 6)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED and entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(cell.workload["why"]) <= 200 and len(entry["why"]) <= 200
    assert "conv" in cell.workload["why"] and "deployed" in cell.workload["why"]


def test_every_width_is_the_published_one_and_reduced_is_the_four_cuts():
    """The catalog's row, key for key; the depth, the leading dense layers, the
    experts held and the vocabulary alone are cut, to the guide's floors."""
    from galvatron_tpu.models import lfm2_moe

    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == REDUCED == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 1, 8, 65536 // 4)
    assert config["router_width"] == want["num_experts"] and config["experts_held_start"] == 0
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"LFM2-8B-A1B"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    preset = lfm2_moe.PUBLISHED["lfm2-8b-a1b"]
    assert {k: preset[k] for k in want} == want and config["source"] == lfm2_moe.LFM2_8B_A1B_SOURCE
    # the file keeps the published list whole; the program is handed it from its second entry on
    fields = cells.config_fields(config)
    assert config["layer_types"] == preset["layer_types"] and len(fields["layer_types"]) == 23
    assert fields["layer_types"] == [{"conv": "conv", "full_attention": "attention"}[t]
                                     for t in preset["layer_types"][1:]]
    assert fields["layer_types"] == lfm2_moe.lfm2_moe_config().layer_types[1:]
    assert fields["layer_types"][:5] == ["conv", "attention", "conv", "conv", "conv"]
    # the program's fields are the published keys but the four in `reduced`
    assert (fields["hidden_size"], fields["num_heads"], fields["num_kv_heads"], fields["head_dim"]) == (2048, 32, 8, 64)
    assert (fields["ffn_hidden"], fields["dense_ffn_hidden"], fields["short_conv_kernel"]) == (1792, 7168, 3)
    assert (fields["num_experts"], fields["experts_held"], fields["experts_per_token"],
            fields["first_dense_layers"], fields["num_layers"], fields["vocab_size"]) == (32, 8, 4, 1, 5, 16384)
    assert (fields["layernorm_eps"], fields["rope_theta"], fields["routed_scaling_factor"]) == (1e-5, 1e6, 1)
    assert (fields["router_score"], fields["router_bias"], fields["norm_topk_prob"], fields["qk_norm"],
            fields["position_type"], fields["tie_embeddings"]) == ("sigmoid", True, True, "head", "rope", True)
    assert "num_shared_experts" not in fields  # none, as published
    # the guide's floors: the leading dense layer once and four that follow it (a whole
    # period: three conv to one attention), 8 routed experts, an eighth of the vocabulary or more
    assert fields["layer_types"][1:5].count("conv") == 3 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= want["vocab_size"]
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    assert {"tie_word_embeddings", "initializer_range", "conv_init", "router_bias_update_rate", "column_layout",
            "router_epsilon", "head_dim"} <= set(config["assumed"])
    assert {"the experts' exchange", "the convolution's window at inference", "packed documents",
            "max_position_embeddings"} <= set(config["not_modelled"])
    assert "4 chips share every layer" in config["deployment"] and "a quarter" in config["deployment"]
    assert config["initializer_range"] == lfm2_moe.INITIALIZER_RANGE
    assert config["tie_word_embeddings"] is lfm2_moe.TIE_WORD_EMBEDDINGS
    assert config["router_bias_update_rate"] == lfm2_moe.ROUTER_BIAS_UPDATE_RATE
    assert (config["reference"], config["flops"]) == ("lfm2_moe_lm", "lfm2_moe")


def test_the_program_built_from_the_file_counts_507_820_288_parameters():
    """ISSUE 46's table, derived here by hand and counted off the program."""
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    expert = 3 * 2048 * 1792
    routed = 8 * expert + 2048 * 32 + 32
    norms, dense = 2 * 2048, 3 * 2048 * 7168
    by_hand = (conv + dense + norms) + (attention + routed + norms) + 3 * (conv + routed + norms) + 16384 * 2048 + 2048
    assert (conv, attention, dense, routed, by_hand) == (16_783_360, 10_485_888, 44_040_192, 88_145_952, 507_820_288)
    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert cfg.layer_kinds() == ("conv.dense", "routed", "conv.routed", "conv.routed", "conv.routed")
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == by_hand
    assert count * 16 / 1e9 == pytest.approx(8.13, abs=0.01)  # GB of state, of a chip's 16
    assert count * 12 / 2 ** 30 == pytest.approx(5.675, abs=0.001)  # what `step_args_gib` reads


def test_the_first_loss_is_derived():
    cell = cells.load_cell(REPO, CELL)
    first = cell.config["checks"]["first_loss"]
    assert "DERIVED" in first["why"] and ("plus" in first) == ("plus_why" in first)
    assert harness.expected_first_loss(cell) == pytest.approx(
        math.log(16384) + 2048 * 0.02 ** 2 / 2 + first.get("plus", 0.0), abs=1e-12)
    assert abs(first.get("plus", 0.0)) < 0.02  # the tied table's own part is small
    assert first["abs"] <= 0.1 and cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand_and_by_the_programs_own_count():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    conv = c.conv_mixer_fwd_flops_a_token(f)
    assert conv == 2 * 2048 * 6144 + 2 * 2048 * 2048  # the gates and the taps are no matmul
    attention = c.attention_mixer_fwd_flops_a_token(f, 8192)
    assert attention["projections"] == 2 * (2 * 2048 * 2048 + 2048 * 2 * 8 * 64)
    assert attention["core"] == 2 * 2 * 8192 * 2048 // 2  # q k^T and p v at 32 x 64, the causal half
    dense, routed = c.mlp_fwd_flops_a_token(f, False), c.mlp_fwd_flops_a_token(f, True)
    assert dense == 3 * 2 * 2048 * 7168
    assert routed == (4 * 8 / 32) * 3 * 2 * 2048 * 1792 + 2 * 2048 * 32  # the even share, the router; no shared one
    head = 2 * 2048 * 16384
    fwd = 4 * conv + sum(attention.values()) + dense + 4 * routed + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert fwd / 1e6 == pytest.approx(432.5, abs=0.05) and cells.flops_a_token(cell) / 1e9 == pytest.approx(1.2975, abs=5e-4)
    assert (c.conv_layers(f), c.routed_blocks(f)) == (4, 4)
    # a change of sequence length moves the attention layer's count alone
    assert c.train_flops_a_token(f, 4096) - c.train_flops_a_token(f, 8192) == -3 * attention["core"] / 2
    # the shares of the forward FLOPs (ISSUE 46's, and the cell's `why`)
    shares = {"conv mixers": 4 * conv, "attention": sum(attention.values()), "dense MLP": dense,
              "routed halves": 4 * routed, "head": head}
    assert {k: round(100 * v / fwd, 1) for k, v in shares.items()} == {
        "conv mixers": 31.0, "attention": 12.6, "dense MLP": 20.4, "routed halves": 20.5, "head": 15.5}
    # the yardstick shares no code with the program's own count, and agrees with it
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert obs_flops.train_step_flops(cfg, 2) == pytest.approx(TOKENS * cells.flops_a_token(cell), rel=1e-12)


def test_the_gate_pass_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.conv_gate_cost(f, TOKENS, "fwd"), c.conv_gate_cost(f, TOKENS, "bwd")
    assert fwd["bytes"] == (3 * 2048 + 2048) * 2 * TOKENS  # [B | C | u] in, C v out, bf16
    assert bwd["bytes"] == (3 * 2048 + 2048 + 3 * 2048) * 2 * TOKENS + 2048 * 3 * 4  # those, the cotangent, d[B C u]
    assert fwd["flops"] == (2 * 3 + 2) * 2048 * TOKENS and bwd["flops"] == (6 * 3 + 4) * 2048 * TOKENS
    # memory bound by two hundred times: 0.328 ms forward, 0.574 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["bytes"] / 819e9, "memory")
    assert flops.least_time_s(bwd, PEAK) == (bwd["bytes"] / 819e9, "memory")
    assert fwd["bytes"] / 819e9 > 200 * fwd["flops"] / 197e12
    assert flops.least_time_s(fwd, PEAK)[0] * 1e3 == pytest.approx(0.3278, abs=1e-3)
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(0.5736, abs=1e-3)


def test_the_grouped_matmuls_cost_by_hand_at_the_cells_rows():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    rows = TOKENS * 4 * 8 // 32  # the even share a block: 2048 rows an expert, four 512-row tiles
    assert rows == 16384 and rows // 8 == 4 * 512
    assert (c.gmm_dims(f, "in"), c.gmm_dims(f, "out")) == ((2048, 2 * 1792), (1792, 2048))
    into, out = c.gmm_cost(f, "in", rows), c.gmm_cost(f, "out", rows)
    assert into["flops"] == 2 * rows * 2048 * 3584 and out["flops"] == 2 * rows * 1792 * 2048
    assert into["bytes"] == (2048 * 3584 + rows * 2048 + rows * 3584) * 2  # ONE expert's kernel, the rows in and out
    assert flops.least_time_s(into, PEAK)[1] == flops.least_time_s(out, PEAK)[1] == "compute"
    assert flops.least_time_s(into, PEAK)[0] * 1e3 == pytest.approx(1.2208, abs=1e-3)
    assert c.gmm_cost(f, "in", 0.0) == {"flops": 0.0, "bytes": 0.0}


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(rows=4 * 16384.0, conv=True):
    """The cell's step as the compiled step labels it: three runs (the conv +
    dense layer, the attention + experts layer, three conv + experts scanned),
    the program's scope names nested under the transforms' wrappers."""
    r0, r1, r2 = (tracing.layers_scope(k) for k in range(3))
    first = "jit(train_step)/jvp(%s)/" % r0
    full = "jit(train_step)/jvp(%s)/" % r1
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r2
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r2
    remat = bwd + "rematted_computation/"
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", full + "pallas_call"): [2e-3, 1],
        label("fusion.5", full + tracing.ATTN_PROJ + "/dot_general"): [1.5e-3, 1],
        label("fusion.6", first + tracing.MLP + "/dot_general"): [3e-3, 1],
        label("fusion.8", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.5e-3, 3],
        label("fusion.9", bwd + tracing.MOE_DISPATCH + "/gather"): [4e-3, 3],
        label("fusion.13", remat + tracing.MOE_COMBINE + "/gather"): [1.5e-3, 3],
        label("fusion.14", remat + tracing.MOE_EXPERTS + "/mul"): [0.5e-3, 3],  # SwiGLU: experts', no kernel
        label("gmm.3", fwd + tracing.MOE_EXPERTS + "/" + tracing.MOE_GMM_IN + "/pallas_call"): [6e-3, 4],
        label("tgmm.4", bwd + tracing.MOE_EXPERTS + "/" + tracing.MOE_GMM_OUT + "/pallas_call"): [4e-3, 4],
        label("fusion.10", fwd + "mul"): [0.5e-3, 3],  # a run's self time
    }
    if conv:
        ops.update({
            label("fusion.1", first + tracing.ATTN_CONV_PROJ + "/dot_general"): [1e-3, 2],
            label("fusion.2", fwd + tracing.ATTN_CONV_PROJ + "/dot_general"): [3e-3, 6],
            label("fusion.3", remat + tracing.ATTN_CONV_PROJ + "/dot_general"): [3e-3, 6],
            label("fusion.4", bwd + tracing.ATTN_CONV_PROJ + "/transpose"): [6e-3, 12],
            label("fusion.11", fwd + tracing.ATTN_CONV_GATE + "/mul"): [2e-3, 3],
            label("fusion.12", remat + tracing.ATTN_CONV_GATE + "/add"): [2e-3, 3],
            label("fusion.15", bwd + tracing.ATTN_CONV_GATE + "/pad"): [4e-3, 3],
        })
    events = [] if rows is None else [
        {"type": "step", "iter": i, "loss": 10.1, "expert_rows_held": rows,
         "expert_rows_held_over_even": 0.95 + 0.05 * i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes_forward_recomputed_and_backward():
    run = handmade()
    assert read("shortconv_proj_ms", run) == pytest.approx(1.0 + 3.0 + 3.0 + 6.0)
    assert read("conv_gate_ms", run) == pytest.approx(2.0 + 2.0 + 4.0)
    # neither name begins the other: the two are disjoint and add up to the convolution mixers
    assert (tracing.ATTN_CONV_PROJ, tracing.ATTN_CONV_GATE) == ("gt.attn.shortconv", "gt.attn.conv_gate")
    assert read("shortconv_proj_ms", run) + read("conv_gate_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.(shortconv|conv_gate)"))
    assert read("lfm2_attn_proj_ms", run) == pytest.approx(1.5) == read("attn_proj_ms", run)
    assert read("lfm2_mlp_ms", run) == pytest.approx(3.0) == read("mlp_ms", run)
    assert read("lfm2_moe_held_dispatch_ms", run) == pytest.approx(0.5 + 4.0 + 1.5) == read("moe_held_dispatch_ms", run)
    assert read("lfm2_moe_held_experts_ms", run) == pytest.approx(0.5 + 6.0 + 4.0) == read("moe_held_experts_ms", run)
    assert read("lfm2_moe_rows_held_over_even", run) == pytest.approx(1.025)
    assert read("flash_ms", run) == pytest.approx(2.0)
    # the layer readers see the nested scopes as the layers', and the parts add up
    rest = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py")
    parts = rest.parts(run)
    assert parts["rest"] == pytest.approx(0.5) and parts[tracing.ATTN_CONV_PROJ] == pytest.approx(13.0)
    assert parts[tracing.ATTN_CONV_GATE] == pytest.approx(8.0) and parts["flash"] == pytest.approx(2.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))
    by_phase = {phase: rest.parts(run, phase)[tracing.ATTN_CONV_GATE]
                for phase in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)}
    assert list(by_phase.values()) == [pytest.approx(2.0), pytest.approx(2.0), pytest.approx(4.0)]


def test_the_two_shares_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 4 * sum(flops.least_time_s(c.conv_gate_cost(f, TOKENS, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(3.605, abs=5e-3)
    run = handmade()
    assert read("conv_gate_roofline", run) == pytest.approx(100 * least / 8e-3)
    # one forward and one backward a layer at their least times read 100, and a recomputed
    # forward, which every run under --checkpoint 1 has, reads less
    for lab, value in run["trace"]["ops_a_step"].items():
        if "gt.attn.conv_gate" in lab:
            which = "bwd" if "transpose" in lab and "rematted" not in lab else "fwd"
            value[0] = 4 * flops.least_time_s(c.conv_gate_cost(f, TOKENS, which), PEAK)[0]
    assert 50.0 < read("conv_gate_roofline", run) < 100.0
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.conv_gate" in k and "rematted" in k)}
    assert read("conv_gate_roofline", run) == pytest.approx(100.0)
    # the grouped matmuls AT THE ROWS THE COUNTER REPORTS: 4 blocks x 16384 rows a step
    run = handmade()
    gmm = 4 * (flops.least_time_s(c.gmm_cost(f, "in", 16384.0), PEAK)[0]
               + flops.least_time_s(c.gmm_cost(f, "out", 16384.0), PEAK)[0])
    assert read("lfm2_moe_held_gmm_roofline", run) == pytest.approx(100 * gmm / 10e-3)
    assert read("lfm2_moe_held_gmm_roofline", run) == read("moe_held_gmm_roofline", run)
    assert read("lfm2_moe_held_gmm_roofline", handmade(2 * 16384.0)) == pytest.approx(
        read("lfm2_moe_held_gmm_roofline", run) / 2, rel=1e-3)  # half the rows, half the least time


def test_a_program_without_the_scopes_or_the_counters_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(None), "trace": None}, {**handmade(None), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    no_conv = handmade(rows=None, conv=False)  # a program with the other scopes and no convolution layer
    for name in ("shortconv_proj_ms", "conv_gate_ms", "conv_gate_roofline", "lfm2_moe_rows_held_over_even",
                 "lfm2_moe_held_gmm_roofline"):
        assert read(name, no_conv) is None
    assert read("lfm2_mlp_ms", no_conv) == pytest.approx(3.0)
    kimi = {**handmade(), "cell": cells.load_cell(REPO, "kimilin-c1-s8k")}
    assert read("conv_gate_roofline", kimi) is None  # its FLOPs module has no conv_gate_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("conv_gate_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    (tmp_path / "benchmarks/configs/lfm2-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-lfm2.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"],
        "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "lfm2-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/lfm2-tiny.json"})
    manifest["workloads"].append({"name": "lfm2-tiny-cell", "config": "lfm2-tiny",
                                  "traffic": "b2-s128-lfm2", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("lfm2-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel check
    holds on the CPU: three runs of layers, 2 of 8 experts held, a tied
    512-row table."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "lfm2-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "lfm2-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "lfm2-tiny-cell")
    cell = cells.load_cell(root, "lfm2-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 46, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

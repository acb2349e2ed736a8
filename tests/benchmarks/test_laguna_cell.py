"""The Laguna cell: its configuration against the catalog's row, its files
through the harness on the CPU at a tiny size, its readers on handmade labels
and events, and its FLOPs, the window kernels' cost and the grouped matmul's
by hand arithmetic. Every assertion is by NAME or by membership: none by a
position in `per_layer` or `workloads`, nor by their lengths, so that a later
PR's appended entries break nothing here."""

import json
import math
import os
import re
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "laguna-c1-s8k"
CONFIG = "laguna-xs.2-d5-e32-v8"
READERS = ("window_attn_ms", "window_attn_roofline", "window_proj_ms", "laguna_attn_proj_ms", "laguna_mlp_ms",
           "laguna_moe_held_dispatch_ms", "laguna_moe_held_experts_ms", "laguna_moe_rows_held_over_even",
           "laguna_moe_held_gmm_roofline", "laguna_moe_shared_ms")
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TOKENS = 8192
BAND = (512 * 8192 - 512 * 511 / 2) / 8192  # the keys a query sees, the mean over the sequence
# the published file with every size made small; the pattern, the switches, the
# reference, the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 5,
        "num_experts": 2, "router_width": 8, "num_experts_per_tok": 2, "vocab_size": 512, "sliding_window": 16}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/laguna.py")


def published():
    """The catalog's row for Laguna-XS.2, as ISSUE 49 quotes it (typed here: the
    catalog lies outside the repository)."""
    return {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                               "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                               "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": PERIOD * 10, "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


# ------------------------------------------------------- the manifest's side
def test_the_cell_finds_its_files():
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(REPO, CELL)
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(REPO, CONFIG)
    test_manifest.check_the_program_receives_the_published_keys(REPO, CELL)


def test_the_cell_reports_its_ten_metrics_and_no_accepted_cell_does():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names
    # the listless readers read it unasked: the two full layers run the flash kernels under their old names
    assert {"flash_ms", "flash_roofline", "layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms",
            "layers_rest_ms", "unscoped_pct", "head_loss_ms", "embed_ms", "optimizer_ms",
            "guard_select_ms", "device_idle_pct"} <= names
    assert not {"collective_ms", "moe_ms", "moe_held_ms", "latent_attn_ms", "mtp_ms", "param_gather_ms",
                "linear_attn_ms", "delta_rule_ms", "kda_rule_ms", "mlp_ms", "mlp_roofline", "attn_proj_ms",
                "ssd_ms", "moe_shared_ms", "kimi_moe_shared_ms", "conv_gate_ms", "lfm2_mlp_ms"} & names
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = {m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")}
            assert not set(READERS) & theirs, other["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    model, moe, attn = "model: models/base.py", "kernels: ops/moe.py", "kernels: ops/attention.py"
    layers = {"window_attn_ms": attn, "window_attn_roofline": attn, "window_proj_ms": model,
              "laguna_attn_proj_ms": model, "laguna_mlp_ms": model, "laguna_moe_held_dispatch_ms": moe,
              "laguna_moe_held_experts_ms": moe, "laguna_moe_rows_held_over_even": moe,
              "laguna_moe_held_gmm_roofline": moe, "laguna_moe_shared_ms": model}
    for name in READERS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
        assert metric["layer"] == layers[name]
        assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in ("window_attn_roofline", "laguna_moe_held_gmm_roofline"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == ("%", "higher")
    assert by_name["laguna_moe_rows_held_over_even"]["source"] == "program_counter"
    # a layer this PR names is one the manifest already had
    assert set(layers.values()) <= {m["layer"] for m in manifest["per_layer"] if m["name"] not in READERS}
    # the entries of these names stand in this order, after every entry of the eleven cells before (a later PR's may follow)
    listed = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in listed if n in READERS] == list(READERS)
    assert listed.index("lfm2_moe_held_gmm_roofline") < listed.index(READERS[0])
    assert cell.chips == 1 and cell.tokens_a_step == TOKENS
    assert cell.workload["traffic"] == "b1-s8k-lrw2k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    assert (cell.traffic["global_batch"], cell.traffic["seq_length"], cell.traffic["warmup_steps"]) == (1, 8192, 6)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == REDUCED and entry["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert len(cell.workload["why"]) <= 200 and len(entry["why"]) <= 200
    assert "window" in cell.workload["why"] and "deployed" in cell.workload["why"]


def test_every_width_is_the_published_one_and_reduced_is_the_three_cuts():
    """The catalog's row, key for key; the depth, the experts held and the
    vocabulary alone are cut, to the guide's floors."""
    from galvatron_tpu.models import laguna

    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == REDUCED == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 32, 100352 // 8)
    assert config["router_width"] == want["num_experts"] and config["experts_held_start"] == 0
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key] and cut["why"]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"Laguna-XS.2"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    preset = laguna.PUBLISHED["laguna-xs.2"]
    assert {k: preset[k] for k in want} == want and config["source"] == laguna.LAGUNA_XS2_SOURCE
    # the file keeps the published lists whole; the program is handed `layer_types` whole and runs its first five
    fields = cells.config_fields(config)
    assert fields["layer_types"] == want["layer_types"] and len(fields["layer_types"]) == 40
    assert fields["layer_types"][:5] == PERIOD + ["full_attention"]
    # the program's fields are the published keys but the three in `reduced`
    assert (fields["hidden_size"], fields["num_heads"], fields["window_num_heads"], fields["num_kv_heads"],
            fields["head_dim"]) == (2048, 48, 64, 8, 128)
    assert fields["window_num_heads"] == want["num_attention_heads_per_layer"][1]
    full, sliding = want["rope_parameters"]["full_attention"], want["rope_parameters"]["sliding_attention"]
    assert (fields["rope_theta"], fields["partial_rotary_factor"]) == (full["rope_theta"], full["partial_rotary_factor"])
    assert fields["rope_scaling"] == {k: full[k] for k in (
        "rope_type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor")}
    assert (fields["window_rope_theta"], fields["window_partial_rotary_factor"]) == (
        sliding["rope_theta"], sliding["partial_rotary_factor"])
    assert (fields["sliding_window"], fields["attn_head_gate"]) == (512, True)
    assert (fields["ffn_hidden"], fields["dense_ffn_hidden"], fields["num_shared_experts"]) == (512, 8192, 1)
    assert (fields["num_experts"], fields["experts_held"], fields["experts_per_token"],
            fields["first_dense_layers"], fields["num_layers"], fields["vocab_size"]) == (256, 32, 8, 1, 5, 12544)
    assert (fields["layernorm_eps"], fields["routed_scaling_factor"]) == (1e-6, 2.5)
    # what the published file has no key for is absent, each a field a reader corrects in THIS file
    assert (fields["router_score"], fields["router_bias"], fields["norm_topk_prob"], fields["qk_norm"],
            fields["shared_expert_gate"], fields["position_type"], fields["tie_embeddings"]) == (
                "softmax", False, True, False, False, "rope", False)
    # the guide's floors: the leading dense layer once and four that follow it (a whole
    # period: three window layers to one full), 8 routed experts or more, an eighth of the vocabulary
    assert fields["layer_types"][1:5].count("sliding_attention") == 3 and config["num_experts"] >= 8
    assert want["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4 and config["vocab_size"] * 8 >= want["vocab_size"]
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    # the six items ISSUE 49 names, each with its evidence AND the other candidate
    six = ("router_score", "qk_norm", "shared_expert_gate", "router_bias", "sliding_window", "gating")
    assert set(six) | {"rule", "initializer_range", "yarn"} <= set(config["assumed"])
    for item in six:
        assert "THE OTHER CANDIDATE" in config["assumed"][item], item
    assert "sigmoid" in config["assumed"]["router_score"] and "34.1 B" in config["assumed"]["gating"]
    assert {"the experts' exchange", "a windowed cache at inference", "packed documents",
            "max_position_embeddings"} <= set(config["not_modelled"])
    assert "8 chips share every layer" in config["deployment"] and "256 rows" in config["deployment"]
    assert config["initializer_range"] == laguna.INITIALIZER_RANGE
    assert (config["reference"], config["flops"]) == ("laguna_lm", "laguna")


def test_the_program_built_from_the_file_counts_691_623_936_parameters():
    """ISSUE 49's table, derived here by hand and counted off the program."""
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    window = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    expert = 3 * 2048 * 512
    routed = 32 * expert + 2048 * 256 + expert  # the held experts, the router, the shared expert
    norms, dense = 2 * 2048, 3 * 2048 * 8192
    by_hand = (full + dense + norms) + 3 * (window + routed + norms) + (full + routed + norms) + 2 * 12544 * 2048 + 2048
    assert (full, window, dense, routed, by_hand) == (29_458_432, 37_879_808, 50_331_648, 104_333_312, 691_623_936)
    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert cfg.layer_kinds() == ("dense", "window.routed", "window.routed", "window.routed", "routed")
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == by_hand
    assert count * 16 / 1e9 == pytest.approx(11.07, abs=0.01)  # GB of state, of a chip's 16
    assert count * 12 / 2 ** 30 == pytest.approx(7.730, abs=0.001)  # what `step_args_gib` reads


def test_the_first_loss_is_derived():
    cell = cells.load_cell(REPO, CELL)
    first = cell.config["checks"]["first_loss"]
    assert "DERIVED" in first["why"] and "plus" not in first and "plus_why" not in first
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(12544) + 2048 * 0.02 ** 2 / 2, abs=1e-12)
    assert harness.expected_first_loss(cell) == pytest.approx(9.847, abs=5e-4)
    assert first["abs"] <= 0.1 and cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand_and_by_the_programs_own_count():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    full, window = c.mixer_fwd_flops_a_token(f, 8192, False), c.mixer_fwd_flops_a_token(f, 8192, True)
    assert full["projections"] == 2 * (2 * 2048 * 6144 + 2048 * 2 * 8 * 128 + 2048 * 48)  # q, o; k, v; the gate
    assert full["core"] == 2 * 2 * 8192 * 6144 // 2  # q k^T and p v at 48 x 128, the causal half
    assert window["projections"] == 2 * (2 * 2048 * 8192 + 2048 * 2 * 8 * 128 + 2048 * 64)
    assert c.band_keys(512, 8192) == BAND == pytest.approx(496.03, abs=5e-3)
    assert window["core"] == 2 * 2 * BAND * 8192  # the exact band at 64 x 128
    assert c.band_keys(512, 100) == 50.5 and c.band_keys(1, 8192) == 1.0  # a wide window is causal; a window of 1 the diagonal
    dense, routed = c.mlp_fwd_flops_a_token(f, False), c.mlp_fwd_flops_a_token(f, True)
    assert dense == 3 * 2 * 2048 * 8192
    assert routed == (8 * 32 / 256 + 1) * 3 * 2 * 2048 * 512 + 2 * 2048 * 256  # the even share, the shared one, the router
    head = 2 * 2048 * 12544
    fwd = 2 * sum(full.values()) + 3 * sum(window.values()) + dense + 4 * routed + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert fwd / 1e6 == pytest.approx(801.8, abs=0.05) and cells.flops_a_token(cell) / 1e9 == pytest.approx(2.4053, abs=5e-4)
    assert (c.window_layers(f), c.routed_blocks(f)) == (3, 4)
    # the shares of the forward FLOPs (ISSUE 49's, and the cell's `why`)
    shares = {"full cores": 2 * full["core"], "full projections": 2 * full["projections"],
              "window projections": 3 * window["projections"], "window cores": 3 * window["core"],
              "dense MLP": dense, "routed + shared": 4 * routed, "head": head}
    assert {k: round(100 * v / fwd, 1) for k, v in shares.items()} == {
        "full cores": 25.1, "full projections": 14.7, "window projections": 28.3, "window cores": 6.1,
        "dense MLP": 12.6, "routed + shared": 6.8, "head": 6.4}
    # a window kernel that did not skip would add the rest of the triangle: 44 % of unpaid work
    triangle = 3 * 2 * 2 * 4096 * 8192
    assert (triangle - 3 * window["core"]) / fwd == pytest.approx(0.441, abs=2e-3)
    # the yardstick shares no code with the program's own count, and agrees with it
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert obs_flops.train_step_flops(cfg, 1) == pytest.approx(TOKENS * cells.flops_a_token(cell), rel=1e-12)


def test_the_window_kernels_cost_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    one = 2 * 64 * 8192 * BAND * 128  # one matmul over the band at 64 heads
    cost = {kind: c.window_kernel_cost(f, kind, 1, 8192) for kind in ("fwd", "bwd")}
    assert [cost[k]["flops"] for k in ("fwd", "bwd")] == [2 * one, 5 * one]
    tensor = lambda heads: heads * 8192 * 128 * 2  # noqa: E731 (a (heads, seq, head_dim) operand in bf16)
    assert cost["fwd"]["bytes"] == 2 * tensor(64) + 2 * tensor(8)  # q, o; k, v at the KEY heads
    assert cost["bwd"]["bytes"] == 3 * tensor(64) + 4 * tensor(8)  # q, do, dq; k, v, dk, dv
    for kind in cost:  # compute bound at 128-wide heads and 8 query heads a key head
        assert flops.least_time_s(cost[kind], PEAK) == (cost[kind]["flops"] / 197e12, "compute")
    assert flops.least_time_s(cost["fwd"], PEAK)[0] * 1e3 == pytest.approx(0.6759, abs=1e-3)
    # a layer under --checkpoint 1: forward twice, backward once; three layers: 9.12 ms a step at the peak
    a_step = 3 * (2 * cost["fwd"]["flops"] + cost["bwd"]["flops"]) / 197e12
    assert a_step * 1e3 == pytest.approx(9.125, abs=0.02)
    assert c.window_kernel_cost(f, "fwd", 2, 8192)["flops"] == 2 * cost["fwd"]["flops"]
    # twice the tokens, twice the band and the edge's 1.6 % (the first 511 queries see fewer keys): the chip
    # check's criterion, where a kernel that walked the causal triangle would read 4
    assert c.window_kernel_cost(f, "fwd", 1, 16384)["flops"] / cost["fwd"]["flops"] == pytest.approx(2.032, abs=1e-3)


def test_the_grouped_matmuls_cost_by_hand_at_the_cells_rows():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    rows = TOKENS * 8 * 32 // 256  # the even share a block: 256 rows an expert, half a 512-row tile
    assert rows == 8192 and rows // 32 == 256
    assert (c.gmm_dims(f, "in"), c.gmm_dims(f, "out")) == ((2048, 2 * 512), (512, 2048))
    into, out = c.gmm_cost(f, "in", rows), c.gmm_cost(f, "out", rows)
    assert into["flops"] == 2 * rows * 2048 * 1024 and out["flops"] == 2 * rows * 512 * 2048
    assert into["bytes"] == (2048 * 1024 + rows * 2048 + rows * 1024) * 2  # ONE expert's kernel, the rows in and out
    assert flops.least_time_s(into, PEAK)[1] == flops.least_time_s(out, PEAK)[1] == "compute"
    assert c.gmm_cost(f, "in", 0.0) == {"flops": 0.0, "bytes": 0.0}


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(rows=4 * 8192.0, window=True):
    """The cell's step as the compiled step labels it: three runs (the full +
    dense layer, three window + experts layers scanned, the full + experts
    layer), the program's scope names nested under the transforms' wrappers."""
    r0, r1, r2 = (tracing.layers_scope(k) for k in range(3))
    first = "jit(train_step)/jvp(%s)/" % r0
    last = "jit(train_step)/jvp(%s)/" % r2
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r1
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r1
    remat = bwd + "rematted_computation/"
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", first + "pallas_call"): [2e-3, 1],
        label("flash_mha_bwd_dkv.8", last + "pallas_call"): [3e-3, 1],
        label("fusion.5", first + tracing.ATTN_PROJ + "/dot_general"): [1.5e-3, 1],
        label("fusion.6", first + tracing.MLP + "/dot_general"): [3e-3, 1],
        label("fusion.8", fwd + tracing.MOE_ROUTER + "/dot_general"): [0.5e-3, 3],
        label("fusion.9", bwd + tracing.MOE_DISPATCH + "/gather"): [4e-3, 3],
        label("fusion.13", remat + tracing.MOE_COMBINE + "/gather"): [1.5e-3, 3],
        label("fusion.14", remat + tracing.MOE_EXPERTS + "/mul"): [0.5e-3, 3],  # SwiGLU: experts', no kernel
        label("fusion.16", fwd + tracing.MOE_SHARED + "/dot_general"): [0.75e-3, 3],
        label("gmm.3", fwd + tracing.MOE_EXPERTS + "/" + tracing.MOE_GMM_IN + "/pallas_call"): [6e-3, 4],
        label("tgmm.4", bwd + tracing.MOE_EXPERTS + "/" + tracing.MOE_GMM_OUT + "/pallas_call"): [4e-3, 4],
        label("fusion.10", fwd + "mul"): [0.5e-3, 3],  # a run's self time
    }
    if window:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_WINDOW + "/dot_general"): [3e-3, 6],
            label("fusion.3", remat + tracing.ATTN_WINDOW + "/dot_general"): [3e-3, 6],
            label("fusion.4", bwd + tracing.ATTN_WINDOW + "/transpose"): [6e-3, 12],
            label("window_attn_fwd.11", fwd + "window_attn_fwd/pallas_call"): [2e-3, 3],
            label("window_attn_fwd.12", remat + "window_attn_fwd/pallas_call"): [2e-3, 3],
            label("window_attn_bwd.13", bwd + "window_attn_bwd/pallas_call"): [8e-3, 3],
        })
    events = [] if rows is None else [
        {"type": "step", "iter": i, "loss": 9.85, "expert_rows_held": rows,
         "expert_rows_held_over_even": 0.95 + 0.05 * i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_window_kernels_names_are_none_of_the_flash_readers():
    """`flash_ms` and `flash_roofline` find kernels by three patterns and price
    every call as a causal triangle at `num_heads`: the window kernels' calls
    match none (`flash_roofline` would read over 105 %), and the flash calls
    match none of the window readers'."""
    flash = cells.load_module(REPO, "benchmarks/layer_metrics/flash_ms.py").KERNELS
    window = cells.load_module(REPO, "benchmarks/layer_metrics/window_attn_ms.py").KERNELS
    ours = ["window_attn_fwd.11", "window_attn_fwd.12", "window_attn_bwd.13"]
    theirs = ["flash_attention.7", "flash_attention:x", "flash_mha_bwd_dkv_1024_512.3", "flash_mha_bwd_dq.4"]
    assert not any(re.search(rx, name) for rx in flash.values() for name in ours)
    assert not any(re.search(rx, name) for rx in window.values() for name in theirs)
    assert all(sum(bool(re.search(rx, name)) for rx in window.values()) == 1 for name in ours)
    run = handmade()
    assert read("flash_ms", run) == pytest.approx(2.0 + 3.0)  # the full layers' alone
    assert read("flash_ms", handmade(window=False)) == read("flash_ms", run)
    # and `flash_roofline` prices them at the FULL layers' 48 heads
    roofline = cells.load_module(REPO, "benchmarks/layer_metrics/flash_roofline.py")
    assert roofline.kernel_shapes(run) == (1, 48, 8192, 128)


def test_the_readers_read_the_programs_scopes_forward_recomputed_and_backward():
    run = handmade()
    assert read("window_proj_ms", run) == pytest.approx(3.0 + 3.0 + 6.0)
    assert read("window_attn_ms", run) == pytest.approx(2.0 + 2.0 + 8.0)
    assert read("laguna_attn_proj_ms", run) == pytest.approx(1.5) == read("attn_proj_ms", run)
    # neither name begins the other: the full and the window layers' projections read apart
    assert (tracing.ATTN_PROJ, tracing.ATTN_WINDOW) == ("gt.attn.proj", "gt.attn.window")
    assert read("laguna_mlp_ms", run) == pytest.approx(3.0) == read("mlp_ms", run)
    assert read("laguna_moe_held_dispatch_ms", run) == pytest.approx(0.5 + 4.0 + 1.5) == read("moe_held_dispatch_ms", run)
    assert read("laguna_moe_held_experts_ms", run) == pytest.approx(0.5 + 6.0 + 4.0) == read("moe_held_experts_ms", run)
    assert read("laguna_moe_shared_ms", run) == pytest.approx(0.75) == read("moe_shared_ms", run)
    assert read("laguna_moe_rows_held_over_even", run) == pytest.approx(1.025)
    # the layer readers see the nested scope as the layers', and the parts add up
    rest = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py")
    parts = rest.parts(run)
    assert parts[tracing.ATTN_WINDOW] == pytest.approx(12.0) and parts["flash"] == pytest.approx(5.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))


def test_the_two_shares_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = {kind: flops.least_time_s(c.window_kernel_cost(f, kind, 1, 8192), PEAK)[0] for kind in ("fwd", "bwd")}
    run = handmade()
    # the calls the trace counts: 6 forward (3 layers, twice under recomputation), 3 backward
    want = 6 * least["fwd"] + 3 * least["bwd"]
    assert want * 1e3 == pytest.approx(9.125, abs=0.02)
    assert read("window_attn_roofline", run) == pytest.approx(100 * want / 12e-3)
    # every call at its least time reads 100
    for lab, value in run["trace"]["ops_a_step"].items():
        kind = next((k for k in least if lab.startswith("window_attn_%s" % k)), None)
        if kind:
            value[0] = value[1] * least[kind]
    assert read("window_attn_roofline", run) == pytest.approx(100.0)
    # the grouped matmuls AT THE ROWS THE COUNTER REPORTS: 4 blocks x 8192 rows a step
    run = handmade()
    gmm = 4 * (flops.least_time_s(c.gmm_cost(f, "in", 8192.0), PEAK)[0]
               + flops.least_time_s(c.gmm_cost(f, "out", 8192.0), PEAK)[0])
    assert read("laguna_moe_held_gmm_roofline", run) == pytest.approx(100 * gmm / 10e-3)
    assert read("laguna_moe_held_gmm_roofline", run) == read("moe_held_gmm_roofline", run)


def test_a_program_without_the_scopes_or_the_counters_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(None), "trace": None}, {**handmade(None), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    no_window = handmade(rows=None, window=False)  # a program with the other scopes and no window layer
    for name in ("window_attn_ms", "window_attn_roofline", "window_proj_ms", "laguna_moe_rows_held_over_even",
                 "laguna_moe_held_gmm_roofline"):
        assert read(name, no_window) is None
    assert read("laguna_mlp_ms", no_window) == pytest.approx(3.0)
    lfm2 = {**handmade(), "cell": cells.load_cell(REPO, "lfm2moe-c1-s8k")}
    assert read("window_attn_roofline", lfm2) is None  # its FLOPs module has no window_kernel_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("window_attn_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    (tmp_path / "benchmarks/configs/laguna-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-laguna.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"],
        "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "laguna-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/laguna-tiny.json"})
    manifest["workloads"].append({"name": "laguna-tiny-cell", "config": "laguna-tiny",
                                  "traffic": "b2-s128-laguna", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("laguna-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel check
    holds on the CPU: three runs of layers, 2 of 8 experts held beside the
    shared one, a window of 16 keys at 128 tokens, two 512-row tables."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "laguna-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "laguna-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "laguna-tiny-cell")
    cell = cells.load_cell(root, "laguna-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 49, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

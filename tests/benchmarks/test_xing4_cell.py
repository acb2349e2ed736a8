"""The Xing4.0 cell: its configuration against the catalog's row, the leaves counted by shapes alone, its FLOPs and
its readers' floors by hand and against the program's, its readers on handmade labels that nest `gt.hc.*` inside
`gt.hc` inside a scanned run, and its files through the harness on the CPU at a tiny size. Every assertion is by
NAME: none by a position in `per_layer` or by the count of cells."""

import json
import math
import os
import shutil

import jax
import numpy as np
import pytest

from benchmarks import cells, flops, harness, trace
from galvatron_tpu.models.xing4 import PUBLISHED, yarn_from_deepseek
from galvatron_tpu.obs import flops as program_flops, telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "xing4-c1-s4k"
CONFIG = "xing4.0-29b-a4b-d5-e8-v8"
READERS = ("hc_ms", "hc_mix_ms", "hc_roofline", "xing_latent_attn_ms", "xing_attn_roofline")
REDUCED = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1), "n_routed_experts": (64, 8),
           "vocab_size": (131072, 16384), "num_nextn_predict_layers": (1, 0)}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference, the FLOPs module and the checks are
# the file's own (the latent head, its yarn, the 4 streams and the 20 steps stay published)
TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32, "num_attention_heads": 2,
        "num_key_value_heads": 2, "q_lora_rank": 48, "kv_lora_rank": 32, "vocab_size": 512, "num_hidden_layers": 3,
        "n_routed_experts": 4, "router_width": 8, "num_experts_per_tok": 2}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/xing4.py")


def published():
    """The catalog's row for Xing4.0-29B-A4B, as ISSUE 66 quotes it (typed here: the catalog lies outside the repository)."""
    return {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2, "hidden_act": "silu", "hidden_size": 3584,
            "intermediate_size": 9216, "kv_lora_rank": 512, "max_position_embeddings": 262144, "model_type": "xing4_0",
            "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
            "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 4,
            "num_hidden_layers": 40, "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
            "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
            "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
            "rope_theta": 10000,
            "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 4096, "type": "yarn"},
            "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
            "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


# ------------------------------------------------------------------ the files
def test_the_cell_reports_its_five_metrics():
    """What this PR owns and no more: its five readers list this cell (what they read where the program has no
    such scope or the configuration no such floor is `test_a_program_without_the_scopes_gives_nothing_to_read`'s)."""
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (CONFIG, "b1-s4k-lrw2k", 1)
    assert (cell.traffic["global_batch"], cell.traffic["seq_length"], cell.traffic["warmup_steps"]) == (1, 4096, 6)
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert CELL in by_name[name]["workloads"] and by_name[name]["moves"] == "tokens_per_s_chip"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["layer"] == ("kernels: ops/attention.py" if name == "xing_attn_roofline"
                                          else "model: models/base.py")
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            ("%", "higher") if name.endswith("_roofline") else ("ms", "lower"))
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names and {"layers_fwd_ms", "layers_bwd_ms", "layers_rest_ms", "head_loss_ms",
                                      "unscoped_pct"} <= names
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"]


def test_every_width_is_the_published_one_and_reduced_is_the_five_cuts():
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    row = published()
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, (was, here) in REDUCED.items():
        cut = config["reduced"][key]
        assert (cut["published"], cut["here"], config[key], row[key]) == (was, here, here, was) and cut["why"], key
    for key, value in row.items():
        if key not in REDUCED:
            assert config[key] == value, key
    assert (config["router_width"], config["experts_held_start"]) == (64, 0)
    for name in ("streams_in_out", "x_tilde_scale", "hc_eps_uses", "sinkhorn_order", "clamp", "init", "yarn", "rope"):
        assert {"here", "evidence", "other_candidate"} <= set(config["assumed"][name]), name
    reference = open(os.path.join(REPO, "benchmarks/references/xing4_lm.py")).read()
    for switch in ("sum_out", "x_scale", "sinkhorn_order", "clamp", "yarn_mscale", "hyper"):
        assert '"%s"' % switch in reference and ("\\\"%s\\\"" % switch in json.dumps(config["assumed"])
                                                 or switch == "hyper")
    assert {"MTP", "the experts' exchange", "long context", "ep_size"} <= set(config["not_modelled"])
    assert "expert parallel 8" in config["deployment"] and "256 rows" in config["deployment"]
    entry = next(c for c in cells.load_json(REPO, cells.MANIFEST)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == PUBLISHED["xing4.0-29b-a4b"]["source"]
    assert entry["source"] == "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"
    # the program's preset is the published file too, and what the file hands it mapped is what the family file maps
    assert {k: v for k, v in PUBLISHED["xing4.0-29b-a4b"].items() if k != "source"} == row
    fields = config["program"]["fields"]
    assert (fields["rope_scaling"], fields["attention_multiplier"]) == yarn_from_deepseek(config["rope_scaling"], 192)
    assert fields["hc_res_clamp"] == [config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]]


def test_the_program_built_from_the_file_counts_the_parameters_the_file_states():
    from galvatron_tpu.models import base as M
    from . import test_manifest

    test_manifest.check_the_program_receives_the_published_keys(REPO, CELL)  # every `program.fields` value reaches it
    cell = cells.load_cell(REPO, CELL)
    cfg = cells.import_attr(cell.config["program"]["config_fn"])(
        cell.config["program"]["preset"], **{**cell.fields, "max_seq_len": 4096})
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    mla = 3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512 + 512 * 32 * 256 + 4096 * 3584
    hyper = 2 * (4 * 3584 * 24 + 24 + 3)
    expert = 3 * 3584 * 1024
    dense = mla + 2 * 3584 + 3 * 3584 * 9216 + hyper
    routed = mla + 2 * 3584 + expert + 3584 * 64 + 64 + 8 * expert + hyper
    assert (mla, hyper, dense, routed) == (28_411_136, 688_182, 128_196_918, 128_426_358)
    total = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert total == dense + 4 * routed + 2 * 16384 * 3584 + 3584 == cell.config["parameters"] == 759_346_446
    assert cfg.layer_kinds() == ("dense",) + ("routed",) * 4 and cfg.mtp_layers == 0 and "mtp" not in shapes
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (4, 20, 1e-6, [-30.0, 30.0])
    assert (cfg.head_dim, cfg.held_experts, cfg.num_experts, cfg.experts_per_token) == (256, (0, 8), 64, 4)
    assert shapes["layers"][1]["hc2"]["phi"].shape == (4 * 3584, 24) and shapes["layers"][0]["hc1"]["a"].shape == (3,)
    assert shapes["layers"][1]["wi"]["kernel"].shape == (8, 3584, 2048)
    assert shapes["lm_head"]["kernel"].shape == (3584, 16384)


def test_the_first_loss_is_the_cross_entropy_alone():
    cell = cells.load_cell(REPO, CELL)
    assert "plus" not in cell.config["checks"]["first_loss"]
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(16384) + 3584 * 0.02 ** 2 / 2, abs=1e-12)
    assert harness.expected_first_loss(cell) == pytest.approx(10.421, abs=1e-3)
    # the routed cells' limit, as ISSUE 66 asked; and the gates start where the comparison sees the mixes
    assert cell.fields["hc_init_gate"] == 1.0
    assert cell.config["checks"]["reference_loss"]["abs"] == 2e-3 and cell.config["checks"]["first_loss"]["abs"] <= 0.1


# ------------------------------------------------------------------ the FLOPs
def test_flops_a_token_by_hand_and_the_programs_own_count():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    parts = c.fwd_flops_a_token_by_part(f, 4096)
    half = 2.0 * 14336 * 24 + 2.0 * 14336 + 2.0 * 16 * 3584 + 2.0 * 14336
    assert half == 860_160 and parts["hyper"] == 10 * half
    assert parts["mla_core"] == 5 * 2.0 * 4096 * 32 * (192 + 128) * 0.5  # the MODEL's products, not the call's 256
    assert parts["held_experts"] == 4 * (4 * 8 / 64) * 3 * 2.0 * 3584 * 1024
    total = sum(parts.values())
    assert total / 1e6 == pytest.approx(952.0, abs=0.05)
    assert cells.flops_a_token(cell) == c.train_flops_a_token(f, 4096) == 3 * total
    assert {k: round(100 * v / total, 1) for k, v in parts.items()} == {
        "mla_projections": 29.8, "mla_core": 22.0, "dense_mlp": 20.8, "shared_experts": 9.3, "held_experts": 4.6,
        "router": 0.2, "hyper": 0.9, "head": 12.3}
    build = cells.import_attr(cell.config["program"]["config_fn"])
    cfg = build(cell.config["program"]["preset"], **{**f, "max_seq_len": 4096})
    assert program_flops.train_step_flops(cfg, 1) / 4096 == pytest.approx(3 * total, rel=1e-12)
    # one stream counts nothing for the mechanism, here and in the program
    one = {**f, "hc_mult": 1}
    assert c.fwd_flops_a_token_by_part(one, 4096)["hyper"] == 0.0
    assert program_flops.hyper_fwd_flops_a_token(hidden=3584, streams=4) == 2 * half
    with pytest.raises(ValueError, match="multi-token-prediction"):
        c.train_flops_a_token({**f, "mtp_layers": 1}, 4096)


def test_the_readers_floors_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    assert c.attn_layers(f) == 5
    wide, row, phi = 14336, 3584, 2 * 4.0 * 14336 * 24  # (Phi once a half's first pass, float32)
    fwd, remat, bwd = (c.hc_cost(f, 4096, which) for which in ("fwd", "remat", "bwd"))
    # a layer's two halves: X read twice and written once a forward half, the body's row out and back
    assert fwd["bytes"] == 4096 * 2 * (3 * wide + 2 * row) * 2 + phi
    assert remat["bytes"] == 4096 * ((3 * wide + 2 * row) + (wide + row)) * 2 + phi
    assert bwd["bytes"] == 4096 * 2 * (5 * wide + 3 * row) * 2 + phi
    half = 2.0 * 14336 * 24 + 2.0 * 14336 + 2.0 * 16 * 3584 + 2.0 * 14336
    assert fwd["flops"] == remat["flops"] == 4096 * 2 * half and bwd["flops"] == 2 * fwd["flops"]
    for cost in (fwd, remat, bwd):
        assert flops.least_time_s(cost, PEAK) == (cost["bytes"] / 819e9, "memory")
    # a half's forward is three and a half passes over the 117 MB array where one stream adds 29 MB once
    assert (fwd["bytes"] - phi) / 2 / (4096 * 14336 * 2) == 3.5 and 4096 * 14336 * 2 == 117_440_512
    a_fwd, a_bwd = c.attn_cost(f, 1, 4096, "fwd"), c.attn_cost(f, 1, 4096, "bwd")
    assert a_fwd["flops"] == 2.0 * 32 * 4096 * 4096 * (192 + 128) * 0.5
    assert a_bwd["flops"] == 2.0 * 32 * 4096 * 4096 * (3 * 192 + 2 * 128) * 0.5
    assert flops.least_time_s(a_fwd, PEAK)[1] == flops.least_time_s(a_bwd, PEAK)[1] == "compute"
    # jax's three kernels at the call's 256: the model's work is 52 % of theirs under full recomputation
    theirs = sum(times * flops.flash_kernel_cost(kind, 1, 32, 4096, 256)["flops"]
                 for kind, times in (("fwd", 2), ("dkv", 1), ("dq", 1)))
    assert (2 * a_fwd["flops"] + a_bwd["flops"]) / theirs == pytest.approx(1472 / 2816)


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(streams=True):
    """The cell's step as the compiled step labels it (read off the chip's trace, PR 66): a scanned run of routed
    layers under its own name, `gt.hc` inside it and `gt.hc.<part>` inside that, the halves' own scopes beside them;
    the widening and the final sum under `gt.hc` outside the runs; jax's flash kernels by their names."""
    r1 = tracing.layers_scope(1)
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r1
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r1
    remat = bwd + "rematted_computation/"
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("fusion.7", fwd + tracing.ATTN_LATENT + "/dot_general"): [10e-3, 4],
        label("fusion.8", bwd + tracing.MOE_EXPERTS + "/dot_general"): [12e-3, 4],
        label("flash_attention.4", fwd + "jit(flash_attention)/pallas_call"): [12e-3, 5],
        label("flash_attention.5", remat + "jit(flash_attention)/pallas_call"): [12e-3, 5],
        label("flash_mha_bwd_dkv_1024_1024.6", bwd + "jit(_flash_attention_bwd_dkv)/pallas_call"): [24e-3, 5],
        label("flash_mha_bwd_dq_1024_1024.7", bwd + "jit(_flash_attention_bwd_dq)/pallas_call"): [18e-3, 5],
        label("fusion.10", fwd + "add"): [0.5e-3, 4],  # a run's self time
    }
    if streams:
        hc = tracing.HC + "/"
        ops.update({
            label("fusion.30", fwd + hc + tracing.HC_COEF + "/dot_general"): [4e-3, 8],
            label("fusion.31", remat + hc + tracing.HC_COEF + "/dot_general"): [3e-3, 8],
            label("fusion.32", bwd + hc + tracing.HC_COEF + "/dot_general"): [11e-3, 8],
            label("fusion.33", fwd + hc + tracing.HC_SINKHORN + "/div"): [1.5e-3, 8],
            label("fusion.34", bwd + hc + tracing.HC_SINKHORN + "/div"): [4e-3, 8],
            label("fusion.35", fwd + hc + tracing.HC_MIX + "/concatenate"): [3e-3, 8],
            label("fusion.36", remat + hc + tracing.HC_MIX + "/concatenate"): [1e-3, 8],
            label("fusion.37", bwd + hc + tracing.HC_MIX + "/reduce_sum"): [8e-3, 8],
            label("fusion.38", "jit(train_step)/jvp(%s)/%s/concatenate" % (tracing.HC, tracing.HC_MIX)): [0.25e-3, 1],
            label("fusion.39", "jit(train_step)/transpose(jvp(%s))/%s/add_any" % (tracing.HC, tracing.HC_MIX)): [0.25e-3, 1],
        })
    events = [{"type": "step", "iter": i, "loss": 10.4, "hc_res_col_err": 1e-6, "hc_stream_gain": 1.5} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes_and_the_generic_ones_still_read_such_labels():
    run = handmade()
    assert read("hc_ms", run) == pytest.approx(4 + 3 + 11 + 1.5 + 4 + 3 + 1 + 8 + 0.5)
    assert read("hc_mix_ms", run) == pytest.approx(12.5) and read("xing_latent_attn_ms", run) == pytest.approx(10)
    # the three nested scopes add up to the whole: what `hc_mix_ms` leaves is the coefficients and the Sinkhorn steps
    assert read("hc_ms", run) - read("hc_mix_ms", run) == pytest.approx((4 + 3 + 11) + (1.5 + 4))
    # the generic readers: the nested scopes are parts of their runs, the widening and the sum of none
    assert read("layers_fwd_ms", run) == pytest.approx(10 + 12 + 0.5 + 4 + 1.5 + 3)
    assert read("layers_remat_ms", run) == pytest.approx(12 + 3 + 1)
    assert read("layers_bwd_ms", run) == pytest.approx(12 + 24 + 18 + 11 + 4 + 8)
    assert read("layers_rest_ms", run) == pytest.approx(0.5) and read("unscoped_pct", run) == pytest.approx(0.0)
    # the names the patterns spell are the program's; `gt.hc` begins its three parts and no other name
    assert (tracing.HC, tracing.HC_COEF, tracing.HC_SINKHORN, tracing.HC_MIX) == (
        "gt.hc", "gt.hc.coef", "gt.hc.sinkhorn", "gt.hc.mix")
    every = [getattr(tracing, n) for n in dir(tracing) if n.isupper() and isinstance(getattr(tracing, n), str)
             and getattr(tracing, n).startswith("gt.")]
    assert sorted(b for b in every if b.startswith(tracing.HC) and b != tracing.HC) == [
        "gt.hc.coef", "gt.hc.mix", "gt.hc.sinkhorn"]
    assert telemetry.HYPER_STEP_FIELDS == ("hc_res_col_err", "hc_stream_gain")
    assert set(telemetry.HYPER_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])


def test_the_shares_count_the_models_work_and_never_pass_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    run = handmade()
    fwd, remat, bwd = (flops.least_time_s(c.hc_cost(f, 4096, w), PEAK)[0] for w in ("fwd", "remat", "bwd"))
    least = 5 * (fwd + remat + bwd)
    assert least * 1e3 == pytest.approx(16.72, abs=0.01)
    assert read("hc_roofline", run) == pytest.approx(100 * least / 36e-3)  # ALL of gt.hc's least over ALL of its time
    a_fwd, a_bwd = (flops.least_time_s(c.attn_cost(f, 1, 4096, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert read("xing_attn_roofline", run) == pytest.approx(100 * 5 * (2 * a_fwd + a_bwd) / 66e-3)
    # no recomputed forward in the trace: one forward a layer
    for lab in [lab for lab in run["trace"]["ops_a_step"] if "rematted_computation" in lab]:
        del run["trace"]["ops_a_step"][lab]
    assert read("hc_roofline", run) == pytest.approx(100 * 5 * (fwd + bwd) / 32e-3)
    assert read("xing_attn_roofline", run) == pytest.approx(100 * 5 * (a_fwd + a_bwd) / 54e-3)
    # fused passes at their floor read 100; XLA's fusions and the kernels at the call's 256 lie under it
    run = handmade()
    for lab, value in run["trace"]["ops_a_step"].items():
        value[0] = least / 8 if tracing.HC in lab and "fusion.3" in lab else value[0]
        value[0] = 0.0 if lab.startswith(("fusion.38", "fusion.39")) else value[0]
    assert read("hc_roofline", run) == pytest.approx(100.0)
    assert 0 < read("hc_roofline", handmade()) < 100 and 0 < read("xing_attn_roofline", handmade()) < 100


def test_a_program_without_the_scopes_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None, not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(), "trace": None}, {**handmade(), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    one_stream = handmade(streams=False)  # the parent's program on this cell's labels: scopes, and none of these
    assert [read(name, one_stream) for name in READERS[:3]] == [None] * 3
    glm = {**handmade(), "cell": cells.load_cell(REPO, "glm47f-c1-s8k")}  # its FLOPs module prices no such floor
    assert read("hc_roofline", glm) is None and read("xing_attn_roofline", glm) is None
    qwen = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s8k")}  # its configuration names no `flops`
    assert read("hc_roofline", qwen) is None and read("xing_attn_roofline", qwen) is None


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = config[key]
    (tmp_path / "benchmarks/configs/xing4-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s32-xing4.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 32, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "xing4-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]), "file": "benchmarks/configs/xing4-tiny.json"})
    manifest["workloads"].append({"name": "xing4-tiny-cell", "config": "xing4-tiny",
                                  "traffic": "b2-s32-xing4", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("xing4-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed files'; only the sizes are the test's.
    Everything but the TPU kernel check holds on the CPU: a dense and two routed layers inside four streams, the
    share of the experts, yarn, the first loss."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "xing4-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "xing4-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "xing4-tiny-cell")
    cell = cells.load_cell(root, "xing4-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 66, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 32)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}
    assert np.isfinite(detail["last_loss"])

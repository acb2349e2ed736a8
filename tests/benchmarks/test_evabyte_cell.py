"""The EvaByte cell: its configuration against the catalog's row, its files through the harness on the CPU at
a tiny size, its readers on handmade labels and events, and its FLOPs and the aggregation's floor by hand and by
brute force over positions. Every assertion is by NAME: none by a position in `per_layer` or by the count of cells."""

import json
import math
import os
import shutil

import jax
import numpy as np
import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "evabyte-c1-s8k"
CONFIG = "evabyte-6.5b-d4"
READERS = ("eva_agg_ms", "eva_agg_roofline", "eva_prep_ms", "eva_proj_ms", "eva_pooled_mass", "eva_mlp_ms",
           "eva_mlp_roofline")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference, the FLOPs module and the checks
# are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 4,
        "head_dim": 16, "window_size": 64, "chunk_size": 8, "max_seq_length": 256}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/evabyte.py")


def published():
    """The catalog's row for EvaByte, as ISSUE 61 quotes it (typed here: the catalog lies outside the repository)."""
    return {"attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
            "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
            "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008, "lazy_init": True,
            "max_position_embeddings": 32768, "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
            "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32,
            "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
            "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048}


# ------------------------------------------------------------------ the files
def test_the_cell_reports_its_seven_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    # the accepted mix ISSUE 61 names, as it is: the trainer's default step, the layers scanned
    assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (CONFIG, "b1-s8k", 1)
    assert (cell.traffic["global_batch"], cell.traffic["seq_length"], cell.traffic["warmup_steps"]) == (1, 8192, 6)
    assert cell.traffic["train_flags"] == ["--checkpoint", "1"]
    assert not os.path.exists(os.path.join(REPO, "benchmarks/traffic/b1-s8k-unrolled.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tokens_per_s_chip"
        assert by_name[name]["layer"] == ("model: models/base.py" if name in ("eva_proj_ms", "eva_mlp_ms", "eva_mlp_roofline")
                                          else "kernels: ops/eva_attention.py")
    assert (by_name["eva_agg_roofline"]["unit"], by_name["eva_agg_roofline"]["better"]) == ("%", "higher")
    assert (by_name["eva_pooled_mass"]["unit"], by_name["eva_pooled_mass"]["source"]) == ("share", "program_counter")
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names and "unscoped_pct" in names and "layers_rest_ms" in names
    # no flash kernel and no `gt.attn.core` runs in this cell: the two readers list the cells that have one
    assert "flash_ms" not in names and "flash_roofline" not in names
    others = [w["name"] for w in manifest["workloads"] if w["name"] != CELL]
    assert by_name["flash_ms"]["workloads"] == by_name["flash_roofline"]["workloads"] == others[:14]
    for other in others:
        assert not set(READERS) & {m["name"] for m in cells.load_cell(REPO, other).metrics("per_layer")}
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"]


def test_every_width_is_the_published_one_and_reduced_is_depth_alone():
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    row = published()
    assert sorted(config["reduced"]) == ["num_hidden_layers"]
    assert (config["reduced"]["num_hidden_layers"]["published"], config["num_hidden_layers"]) == (32, 4)
    for key, value in row.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert {"pooling", "phi_mu_init", "rope", "pred_head_weights", "fp32_skip_add"} <= set(config["assumed"])
    assert config["head_dim"] == 4096 // 32 and "head_dim" in config["assumed"]
    entry = next(c for c in cells.load_json(REPO, cells.MANIFEST)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    # the program's preset is the published file too
    from galvatron_tpu.models.evabyte import PUBLISHED

    preset = PUBLISHED["evabyte-6.5b"]
    assert {k: v for k, v in preset.items() if k != "source"} == {k: row[k] for k in preset if k != "source"}


def test_the_program_built_from_the_file_counts_821_366_784_parameters():
    from galvatron_tpu.models import base as M

    cell = cells.load_cell(REPO, CELL)
    cfg = cells.import_attr(cell.config["program"]["config_fn"])(
        cell.config["program"]["preset"], **{**cell.fields, "max_seq_len": 8192})
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128
    assert layer == 202_391_552
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 4 * layer + 320 * 4096 + 4096 * 2560 + 4096 \
        == 821_366_784
    assert cfg.layer_kinds() == ("eva.dense",) * 4 and cfg.pred_heads == 8 and cfg.norm_zero_centered
    assert (cfg.eva_window, cfg.eva_chunk, cfg.rope_theta, cfg.init_std) == (2048, 16, 1e5, 0.01275)
    assert shapes["lm_head"]["kernel"].shape == (4096, 8 * 320) and not cfg.tie_embeddings


def test_the_first_loss_is_that_of_eight_alike_heads_over_320_classes():
    cell = cells.load_cell(REPO, CELL)
    variance = 4096 * 0.01275 ** 2
    assert variance == pytest.approx(0.66586, abs=1e-5)
    second_order = -(math.exp(variance) - 1) / (2 * 320)
    assert cell.config["checks"]["first_loss"]["plus"] == pytest.approx(second_order, abs=5e-5)
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(320) + variance / 2 - 0.0015, abs=1e-9)
    assert cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------------ the FLOPs
def brute_pairs(seq, window, chunk):
    return sum((t % window) + 1 + (t // window) * (window // chunk) for t in range(seq))


@pytest.mark.parametrize("seq,window,chunk", [(8192, 2048, 16), (32768, 2048, 16), (3000, 2048, 16), (2048, 2048, 16),
                                              (100, 2048, 16), (4096 + 16, 1024, 8)])
def test_the_pairs_are_counted_exactly(seq, window, chunk):
    from galvatron_tpu.obs import flops as program_flops

    assert costs().eva_pairs(seq, window, chunk) == brute_pairs(seq, window, chunk)
    assert program_flops.eva_pairs(seq, window, chunk) == brute_pairs(seq, window, chunk)


def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    assert c.eva_pairs(8192, 2048, 16) == 9_965_568  # 1216.5 a query: 1024.5 of its window, 192 pooled
    assert c.eva_pairs(8192, 2048, 16) / 8192 == 1024.5 + 192
    mixer = c.mixer_fwd_flops_a_token(f, 8192)
    assert mixer == {"projections": 2.0 * 4096 * 4096 * 4, "aggregation": 2 * 2.0 * 1216.5 * 4096,
                     "pooling": 2 * 2.0 * 4096}
    mlp, head = 2.0 * 4096 * 2 * 11008 + 2.0 * 11008 * 4096, 2.0 * 4096 * 2560
    total = 4 * (sum(mixer.values()) + mlp) + head
    assert cells.flops_a_token(cell) == c.train_flops_a_token(f, 8192) == 3 * total
    assert 3 * total / 1e9 == pytest.approx(5.159, abs=5e-4)
    shares = {"mlp": 4 * mlp / total, "proj": 4 * mixer["projections"] / total,
              "agg": 4 * mixer["aggregation"] / total, "head": head / total}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {"mlp": 62.9, "proj": 31.2, "agg": 4.6, "head": 1.2}
    # at the published 32768 a query of the last window meets 1920 pooled keys, 960 on average
    assert c.eva_pairs(32768, 2048, 16) / 32768 == 1024.5 + 960


def test_the_aggregations_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.eva_cost(f, 8192, "fwd", 8192), c.eva_cost(f, 8192, "bwd", 8192)
    assert fwd["flops"] == 2 * 2.0 * 9_965_568 * 32 * 128 and bwd["flops"] == 2.5 * fwd["flops"]
    assert fwd["flops"] / 1e9 == pytest.approx(163.3, abs=0.05)  # the issue's 163 GFLOP a layer forward
    row = 4096 * 2  # a position's q, k, v or output row, bf16
    assert fwd["bytes"] == (4 * 8192 + 2 * 512) * row  # q, k, v read, the output written; K~, V~ a chunk
    assert bwd["bytes"] == (8 * 8192 + 4 * 512) * row  # those and do read; dq, dk, dv and dK~, dV~ written
    assert c.eva_cost(f, 8192, "fwd") == fwd  # one sequence where no length is given
    assert c.eva_cost(f, 16384, "fwd", 8192)["flops"] == 2 * fwd["flops"]  # two rows of 8192, not one of 16384
    # compute bound at the chip's peaks: 0.83 ms forward, 2.07 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["flops"] / 197e12, "compute")
    assert flops.least_time_s(fwd, PEAK)[0] * 1e3 == pytest.approx(0.829, abs=2e-3)
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(2.072, abs=2e-3)
    assert all(v > 0 for v in (*fwd.values(), *bwd.values()))


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, new_parts=True):
    """The cell's step as the compiled step labels it: one scanned run of four layers, the program's scope
    names nested under the transforms' and the scan's wrappers."""
    r0 = tracing.layers_scope(0)
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r0
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r0
    remat = bwd + "rematted_computation/"
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("fusion.7", fwd + tracing.MLP + "/dot_general"): [10e-3, 4],
        label("fusion.9", bwd + tracing.MLP + "/dot_general"): [20e-3, 4],
        label("fusion.10", fwd + "add"): [0.5e-3, 4],  # the run's self time
    }
    if new_parts:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_EVA + "/dot_general"): [4e-3, 4],
            label("fusion.3", bwd + tracing.ATTN_EVA + "/dot_general"): [8e-3, 4],
            label("fusion.4", fwd + tracing.ATTN_EVA_PREP + "/reduce_sum"): [1e-3, 4],
            label("fusion.5", bwd + tracing.ATTN_EVA_PREP + "/mul"): [2e-3, 4],
            label("eva_agg_fwd.6", fwd + tracing.ATTN_EVA_AGG + "/jit(_forward)/pallas_call"): [12e-3, 4],
            label("eva_agg_fwd.11", remat + tracing.ATTN_EVA_AGG + "/jit(_forward)/pallas_call"): [12e-3, 4],
            label("eva_agg_bwd.12", bwd + tracing.ATTN_EVA_AGG + "/jit(_backward)/pallas_call"): [30e-3, 4],
            label("fusion.13", bwd + tracing.ATTN_EVA_AGG + "/reduce_sum"): [1e-3, 4],  # delta
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 6.1, "eva_pooled_mass": 0.15 + 0.01 * i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("eva_proj_ms", run) == pytest.approx(12.0)  # neither the pooling nor the aggregation
    assert read("eva_prep_ms", run) == pytest.approx(3.0)
    assert read("eva_agg_ms", run) == pytest.approx(55.0)  # forward, recomputed, backward, delta
    assert read("eva_proj_ms", run) + read("eva_prep_ms", run) + read("eva_agg_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.eva"))
    assert read("eva_pooled_mass", run) == pytest.approx(0.165)
    # the dense SwiGLU half under its family's name: `mlp_ms`' and `mlp_roofline`'s readers
    assert read("eva_mlp_ms", run) == read("mlp_ms", run) == pytest.approx(30.0)
    assert read("eva_mlp_roofline", run) == read("mlp_roofline", run) and 0 < read("eva_mlp_roofline", run)
    assert read("flash_ms", run) is None and read("flash_roofline", run) is None  # no kernel of theirs, no gt.attn.core
    assert read("window_attn_ms", run) is None and read("mlp_ms", run) == pytest.approx(30.0)
    assert telemetry.EVA_STEP_FIELDS == ("eva_pooled_mass",)
    assert set(telemetry.EVA_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    assert {"eva_layers", "eva_windows", "eva_pooled_keys"} <= set(telemetry.EVENT_SCHEMAS["compile"][1])
    # the names the patterns spell are the program's; the first begins the other two, which go on where no
    # pattern ends a name, and all three are one word to the readers that name a run's parts
    names = (tracing.ATTN_EVA, tracing.ATTN_EVA_PREP, tracing.ATTN_EVA_AGG)
    assert names == ("gt.attn.eva", "gt.attn.eva_prep", "gt.attn.eva_agg")
    every = [getattr(tracing, n) for n in dir(tracing) if n.isupper() and isinstance(getattr(tracing, n), str)
             and getattr(tracing, n).startswith("gt.")]
    assert not any(b.startswith(a) and b not in names for a in names for b in every)
    assert "gt.attn.core" not in every
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert parts["rest"] == pytest.approx(0.5) and parts[tracing.ATTN_EVA_AGG] == pytest.approx(55.0)
    assert parts[tracing.ATTN_EVA] == pytest.approx(12.0) and parts[tracing.ATTN_EVA_PREP] == pytest.approx(3.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))
    assert read("unscoped_pct", run) == pytest.approx(0.0)


def test_the_share_of_the_floor_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 4 * sum(flops.least_time_s(c.eva_cost(f, 8192, w, 8192), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(11.60, abs=0.01)
    run = handmade()
    assert read("eva_agg_roofline", run) == pytest.approx(100 * least / 55e-3)
    for lab, value in run["trace"]["ops_a_step"].items():
        if "eva_agg_" in lab.split(":")[0]:  # the kernels at their floor
            which = "bwd" if "eva_agg_bwd" in lab else "fwd"
            value[0] = 4 * flops.least_time_s(c.eva_cost(f, 8192, which, 8192), PEAK)[0]
    assert 50.0 < read("eva_agg_roofline", run) < 100.0  # a recomputed forward and delta read under 100
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.eva_agg" in k and ("rematted" in k or "reduce_sum" in k))}
    assert read("eva_agg_roofline", run) == pytest.approx(100.0)


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None, not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    parent = handmade(counters=False, new_parts=False)  # gt.mlp and the runs, nothing new
    assert [read(name, parent) for name in READERS[:5]] == [None] * 5
    assert read("eva_mlp_ms", parent) == pytest.approx(30.0)  # (`gt.mlp` is older than this PR)
    phi4 = {**handmade(), "cell": cells.load_cell(REPO, "phi4flash-c1-s8k")}
    assert read("eva_agg_roofline", phi4) is None  # its FLOPs module has no eva_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("eva_agg_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    (tmp_path / "benchmarks/configs/eva-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s256-eva.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 256, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "eva-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]), "file": "benchmarks/configs/eva-tiny.json"})
    manifest["workloads"].append({"name": "eva-tiny-cell", "config": "eva-tiny",
                                  "traffic": "b2-s256-eva", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("eva-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed files'; only the sizes are the
    test's. Everything but the TPU kernel check holds on the CPU: four scanned layers, four windows of eight
    chunks a sequence, the head of eight predictions, the derived first loss."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "eva-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "eva-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "eva-tiny-cell")
    cell = cells.load_cell(root, "eva-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 61, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(320) + 64 * 0.01275 ** 2 / 2 - 0.0015, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 256)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}
    assert np.isfinite(detail["last_loss"])

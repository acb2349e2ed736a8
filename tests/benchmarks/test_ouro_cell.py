"""The Ouro cell: its configuration against the catalog's row, the leaves counted by shapes alone, its FLOPs by
hand and against the program's, its readers on handmade labels that nest the layer runs inside the loop's scan
(and the generic readers on the same labels), and its files through the harness on the CPU at a tiny size. Every
assertion is by NAME: none by a position in `per_layer` or by the count of cells."""

import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import flops as program_flops, telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ouro-c1-s4k"
CONFIG = "ouro-2.6b-d6"
READERS = ("ouro_loop_ms", "ouro_attn_ms", "ouro_attn_roofline", "ouro_mlp_ms", "ouro_mlp_roofline",
           "ouro_post_norm_ms", "ouro_exit_ms", "exit_step_mean")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the switches, the reference, the FLOPs module and the checks
# are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 2, "num_key_value_heads": 2,
        "head_dim": 32, "vocab_size": 512, "num_hidden_layers": 3, "total_ut_steps": 3}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/ouro.py")


def published():
    """The catalog's row for Ouro-2.6B, as ISSUE 64 quotes it (typed here: the catalog lies outside the repository)."""
    return {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
            "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
            "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
            "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
            "use_sliding_window": False, "vocab_size": 49152}


# ------------------------------------------------------------------ the files
def test_the_cell_reports_its_eight_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    # the accepted mix ISSUE 64 names, as it is
    assert (cell.workload["config"], cell.workload["traffic"], cell.chips) == (CONFIG, "b1-s4k", 1)
    assert (cell.traffic["global_batch"], cell.traffic["seq_length"], cell.traffic["warmup_steps"]) == (1, 4096, 6)
    assert cell.traffic["train_flags"] == ["--checkpoint", "1"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "tokens_per_s_chip"
        assert by_name[name]["layer"] == ("kernels: ops/attention.py" if "attn" in name else "model: models/base.py")
    for name in ("ouro_attn_roofline", "ouro_mlp_roofline"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == ("%", "higher")
    assert (by_name["exit_step_mean"]["unit"], by_name["exit_step_mean"]["source"]) == ("passes", "program_counter")
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert set(READERS) <= names and {"layers_fwd_ms", "layers_bwd_ms", "head_loss_ms", "unscoped_pct"} <= names
    # the standing attention and MLP shares price `num_layers` once: a looped cell stays off their lists
    assert not names & {"flash_ms", "flash_roofline", "mlp_ms", "mlp_roofline", "attn_proj_ms"}
    for other in (w["name"] for w in manifest["workloads"] if w["name"] != CELL):
        assert not set(READERS) & {m["name"] for m in cells.load_cell(REPO, other).metrics("per_layer")}
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"]


def test_every_width_is_the_published_one_and_reduced_is_depth_alone():
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    row = published()
    assert sorted(config["reduced"]) == ["num_hidden_layers"]
    assert (config["reduced"]["num_hidden_layers"]["published"], config["num_hidden_layers"]) == (48, 6)
    for key, value in row.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert config["total_ut_steps"] == 4
    assert {"post_norm", "loop_norm", "exit_gate", "exit_entropy_coef", "attention_bias", "rope", "initializer_range",
            "training_length"} <= set(config["assumed"])
    for name, entry in config["assumed"].items():
        if name != "weights":
            assert {"here", "evidence", "other_candidate"} <= set(entry), name
    assert (config["initializer_range"], config["exit_entropy_coef"]) == (0.02, 0.1)
    entry = next(c for c in cells.load_json(REPO, cells.MANIFEST)["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # the program's preset is the published file too
    from galvatron_tpu.models.ouro import PUBLISHED

    preset = PUBLISHED["ouro-2.6b"]
    assert {k: v for k, v in preset.items() if k != "source"} == row


def test_the_program_built_from_the_file_counts_509_661_185_parameters():
    from galvatron_tpu.models import base as M
    from . import test_manifest

    test_manifest.check_the_program_receives_the_published_keys(REPO, CELL)  # every `program.fields` value reaches it
    cell = cells.load_cell(REPO, CELL)
    cfg = cells.import_attr(cell.config["program"]["config_fn"])(
        cell.config["program"]["preset"], **{**cell.fields, "max_seq_len": 4096})
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(shapes)) == 6 * layer + 2 * 49152 * 2048 + 2048 + 2049 \
        == 509_661_185
    assert (cfg.loop_steps, cfg.post_norm, cfg.exit_gate, cfg.exit_entropy_coef) == (4, True, True, 0.1)
    assert cfg.layer_kinds() == ("dense",) * 6 and not cfg.tie_embeddings and not cfg.qkv_bias
    assert set(shapes["layers"][0]) == {"ln1", "ln1_post", "ln2", "ln2_post", "wqkv", "wo", "wi", "wo_mlp"}
    assert shapes["exit_gate"]["kernel"].shape == (2048, 1) and shapes["exit_gate"]["bias"].shape == (1,)
    assert shapes["lm_head"]["kernel"].shape == (2048, 49152)


def test_the_first_loss_is_the_cross_entropy_less_a_tenth_of_the_gates_entropy():
    cell = cells.load_cell(REPO, CELL)
    variance = 2048 * 0.02 ** 2
    # E[h2(sigmoid(z))], z ~ N(0, variance), by quadrature; three stages of the chain rule at 1, 1/2, 1/4
    z = np.linspace(-12, 12, 200001) * math.sqrt(variance)
    lam = 1 / (1 + np.exp(-z))
    h2 = -(lam * np.log(lam) + (1 - lam) * np.log(1 - lam))
    mean_h2 = float(np.sum(h2 * np.exp(-0.5 * z * z / variance)) * (z[1] - z[0]) / math.sqrt(2 * math.pi * variance))
    assert mean_h2 == pytest.approx(0.6130, abs=1e-4)
    assert cell.config["checks"]["first_loss"]["plus"] == pytest.approx(-0.1 * 1.75 * mean_h2, abs=5e-4)
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(49152) + variance / 2 - 0.107, abs=1e-9)
    assert cell.config["checks"]["reference_loss"]["abs"] <= 2e-3 and cell.config["checks"]["first_loss"]["abs"] <= 0.1


# ------------------------------------------------------------------ the FLOPs
def test_flops_a_token_by_hand_count_applications_not_layers():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    assert c.applications(f) == 24
    layer = c.layer_fwd_flops_a_token(f, 4096)
    assert layer == {"projections": 4 * 2.0 * 2048 * 2048, "scores": 2.0 * 4096 * 2048, "mlp": 3 * 2.0 * 2048 * 5632}
    assert {k: round(v / 1e6, 2) for k, v in layer.items()} == {"projections": 33.55, "scores": 16.78, "mlp": 69.21}
    head = 2.0 * 2048 * 49152
    total = 24 * sum(layer.values()) + 4 * head
    assert cells.flops_a_token(cell) == c.train_flops_a_token(f, 4096) == 3 * total
    assert 3 * total / 1e9 == pytest.approx(11.02, abs=5e-3)
    shares = {"mlp": 24 * layer["mlp"] / total, "proj": 24 * layer["projections"] / total,
              "scores": 24 * layer["scores"] / total, "heads": 4 * head / total}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {"mlp": 45.2, "proj": 21.9, "scores": 11.0, "heads": 21.9}
    # four times the plain stack of the same six layers and one head, and the program's own count to 1e-12
    plain = {**f, "loop_steps": 1}
    assert c.train_flops_a_token(f, 4096) == 4 * c.train_flops_a_token(plain, 4096)
    assert flops.train_flops_a_token(f, 4096) == c.train_flops_a_token(plain, 4096)  # the dense decoder's yardstick
    build = cells.import_attr(cell.config["program"]["config_fn"])
    cfg = build(cell.config["program"]["preset"], **{**f, "max_seq_len": 4096})
    assert program_flops.train_step_flops(cfg, 1) / 4096 == pytest.approx(3 * total, rel=1e-12)
    # the `layer_run` event's rows follow: the run's 24 applications, then the four heads
    from galvatron_tpu import HybridParallelConfig

    runs = program_flops.run_fwd_flops(cfg, HybridParallelConfig.uniform(1, 6, checkpoint=1, global_bsz=1))
    assert runs == pytest.approx([4096 * 24 * sum(layer.values()), 4096 * 4 * head], rel=1e-12)


def test_the_readers_floors_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.attn_cost(f, 1, 4096, "fwd"), c.attn_cost(f, 1, 4096, "bwd")
    assert fwd["flops"] == 2 * 2.0 * 2048 * 4096 * 4096 * 0.5 and bwd["flops"] == 2.5 * fwd["flops"]
    assert fwd == flops.flash_kernel_cost("core_fwd", 1, 16, 4096, 128)  # the standing model's form of one layer
    assert bwd == flops.flash_kernel_cost("core_bwd", 1, 16, 4096, 128)
    assert flops.least_time_s(fwd, PEAK)[1] == flops.least_time_s(bwd, PEAK)[1] == "compute"
    assert c.mlp_train_flops(f, 4096) == 24 * 4096 * 3 * 2.0 * 2048 * 5632 * 3


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, looped=True):
    """The cell's step as the compiled step labels it (read off the chip's trace, PR 64): the loop's scan
    entered under the first run's name, which the transforms wrap, and `gt.loop` inside it, which they do not;
    inside the scan's body the scanned run of six layers under its own name, the program's scope names nested
    under the scan's wrappers."""
    r0 = tracing.layers_scope(0)
    inner = "/while/body/closed_call/%s/while/body/closed_call/" % r0
    if looped:
        fwd = "jit(train_step)/jvp(%s)/%s" % (r0, tracing.LOOP) + inner
        bwd = "jit(train_step)/transpose(jvp(%s))/%s" % (r0, tracing.LOOP) + inner + "checkpoint/"
        between = "jit(train_step)/jvp(%s)/%s/while/body/closed_call/" % (r0, tracing.LOOP)
    else:  # a plain stack: one scan
        fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r0
        bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r0
        between = fwd
    remat = bwd + "rematted_computation/"
    core = tracing.attn_core_scope()
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 4],
        label("fusion.22", "jit(train_step)/transpose(jvp(%s))/dot_general" % tracing.HEAD_LOSS): [10e-3, 8],
        label("fusion.7", fwd + tracing.MLP + "/dot_general"): [40e-3, 24],
        label("fusion.8", remat + tracing.MLP + "/dot_general"): [40e-3, 24],
        label("fusion.9", bwd + tracing.MLP + "/dot_general"): [80e-3, 24],
        label("fusion.2", fwd + tracing.ATTN_PROJ + "/dot_general"): [4e-3, 24],
        label("fusion.3", bwd + tracing.ATTN_PROJ + "/dot_general"): [8e-3, 24],
        label("causal_attn_fwd.4", fwd + core + "/jit(_forward)/pallas_call"): [12e-3, 24],
        label("causal_attn_fwd.5", remat + core + "/jit(_forward)/pallas_call"): [12e-3, 24],
        label("causal_attn_bwd.6", bwd + core + "/jit(_backward)/pallas_call"): [30e-3, 24],
        label("fusion.10", fwd + "add"): [0.5e-3, 24],  # a run's self time
        label("fusion.11", between + "mul"): [0.25e-3, 4],  # the norm between passes
    }
    if looped:
        ops.update({
            label("fusion.12", fwd + tracing.NORM_POST + "/mul"): [1e-3, 48],
            label("fusion.13", bwd + tracing.NORM_POST + "/mul"): [2e-3, 48],
            label("fusion.14", "jit(train_step)/jvp(%s)/reduce_sum" % tracing.EXIT): [0.25e-3, 1],
            label("fusion.15", "jit(train_step)/transpose(jvp(%s))/mul" % tracing.EXIT): [0.5e-3, 1],
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 11.1, "exit_step_mean": 1.8 + 0.1 * i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes_and_the_generic_ones_still_read_such_labels():
    run = handmade()
    # the generic readers on labels that nest the run inside the loop inside the outer scan
    assert read("layers_fwd_ms", run) == pytest.approx(40 + 4 + 12 + 0.5 + 0.25 + 1)
    assert read("layers_remat_ms", run) == pytest.approx(40 + 12)
    assert read("layers_bwd_ms", run) == pytest.approx(80 + 8 + 30 + 2)
    assert read("head_loss_ms", run) == pytest.approx(15.0) and read("optimizer_ms", run) == pytest.approx(1.0)
    assert read("unscoped_pct", run) == pytest.approx(0.0)
    # the loop encloses the runs: it is their three phases together
    assert read("ouro_loop_ms", run) == pytest.approx(
        read("layers_fwd_ms", run) + read("layers_remat_ms", run) + read("layers_bwd_ms", run))
    assert read("ouro_attn_ms", run) == pytest.approx(54.0) and read("ouro_mlp_ms", run) == pytest.approx(160.0)
    assert read("ouro_post_norm_ms", run) == pytest.approx(3.0) and read("ouro_exit_ms", run) == pytest.approx(0.75)
    assert read("exit_step_mean", run) == pytest.approx(1.95)
    assert telemetry.LOOP_STEP_FIELDS == ("loss_ce_first", "loss_ce_last", "exit_step_mean", "exit_entropy")
    assert set(telemetry.LOOP_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    assert "loop_steps" in telemetry.EVENT_SCHEMAS["run_start"][1]
    # the names the patterns spell are the program's, and none begins another
    assert (tracing.LOOP, tracing.NORM_POST, tracing.EXIT) == ("gt.loop", "gt.norm.post", "gt.exit")
    every = [getattr(tracing, n) for n in dir(tracing) if n.isupper() and isinstance(getattr(tracing, n), str)
             and getattr(tracing, n).startswith("gt.")]
    assert not any(b.startswith(a) and b != a for a in (tracing.LOOP, tracing.NORM_POST, tracing.EXIT) for b in every)
    # the same readers on a plain stack's labels: the generic ones as ever, this PR's nothing
    plain = handmade(looped=False)
    assert read("layers_bwd_ms", plain) == pytest.approx(80 + 8 + 30) and read("layers_remat_ms", plain) == pytest.approx(52.0)
    assert [read(n, plain) for n in ("ouro_loop_ms", "ouro_post_norm_ms", "ouro_exit_ms")] == [None] * 3


def test_the_shares_count_24_applications_and_never_pass_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    run = handmade()
    fwd, bwd = (flops.least_time_s(c.attn_cost(f, 1, 4096, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert read("ouro_attn_roofline", run) == pytest.approx(100 * 24 * (2 * fwd + bwd) / 54e-3)
    mlp_least = 24 * 4096 * 3 * 2.0 * 2048 * 5632 * 3 / 197e12
    assert read("ouro_mlp_roofline", run) == pytest.approx(100 * mlp_least / 160e-3)
    # the standing readers on the same run count `num_layers` once: a quarter of the work
    by_layers = read("flash_roofline", run), read("mlp_roofline", run)
    assert by_layers[0] == pytest.approx(read("ouro_attn_roofline", run) / 4)
    assert by_layers[1] == pytest.approx(read("ouro_mlp_roofline", run) / 4)
    # kernels and matmuls at their floor read 100, anything slower under it
    for lab, value in run["trace"]["ops_a_step"].items():
        if "causal_attn_" in lab.split(":")[0]:
            value[0] = 24 * (bwd if "causal_attn_bwd" in lab else fwd)
        if "gt.mlp" in lab:
            value[0] = mlp_least * (1 / 3 if "rematted" in lab or "transpose" not in lab else 2 / 3)
    assert read("ouro_attn_roofline", run) == pytest.approx(100.0)
    assert read("ouro_mlp_roofline", run) == pytest.approx(75.0)  # the recomputed forward is in the time alone
    assert 0 < read("ouro_attn_roofline", handmade()) < 100 and 0 < read("ouro_mlp_roofline", handmade()) < 100


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None, not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    qwen = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s8k")}  # not looped: the standing pair reads it
    assert read("ouro_attn_ms", qwen) is None and read("ouro_attn_roofline", qwen) is None
    assert read("ouro_mlp_roofline", qwen) is None  # its configuration names no `flops`
    eva = {**handmade(), "cell": cells.load_cell(REPO, "evabyte-c1-s8k")}
    assert read("ouro_mlp_roofline", eva) is None  # its FLOPs module has no `mlp_train_flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    config["reduced"]["num_hidden_layers"]["here"] = TINY["num_hidden_layers"]
    (tmp_path / "benchmarks/configs/ouro-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s32-ouro.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 32, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "ouro-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]), "file": "benchmarks/configs/ouro-tiny.json"})
    manifest["workloads"].append({"name": "ouro-tiny-cell", "config": "ouro-tiny",
                                  "traffic": "b2-s32-ouro", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("ouro-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed files'; only the sizes are the
    test's. Everything but the TPU kernel check holds on the CPU: three scanned layers run three times, the
    gate, the expected loss, the derived first loss."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "ouro-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "ouro-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "ouro-tiny-cell")
    cell = cells.load_cell(root, "ouro-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 64, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    # (three passes here: the chain rule's stages at 1 and 1/2, and z's variance is 64 x 0.02^2; inside the limit)
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2 - 0.107, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 32)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}
    assert np.isfinite(detail["last_loss"])

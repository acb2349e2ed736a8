"""The Phi-4-mini-flash cell: its configuration against the catalog's row, its
files through the harness on the CPU at a tiny size, its readers on handmade
labels and events, and its FLOPs and the selective scan's floor by hand
arithmetic. Every assertion is by NAME: none by a position in `per_layer` or by
the count of cells."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "phi4flash-c1-s8k"
CONFIG = "phi-4-mini-flash-d6-v8"
READERS = ("selscan_ms", "selscan_roofline", "mamba_mixer_ms", "gmu_ms", "diff_combine_ms", "phi4_attn_proj_ms",
           "phi4_mlp_ms", "selscan_state_abs_max", "published_mib")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CUT = [0, 1, 16, 17, 18, 19]
# the published file with every size made small; the pattern, the cut, the switches, the
# reference, the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "mamba_d_state": 4, "mamba_dt_rank": 4, "sliding_window": 8, "vocab_size": 512,
        "max_position_embeddings": 128}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/phi4flash.py")


def published():
    """The catalog's row for Phi-4-mini-flash-reasoning, as ISSUE 57 quotes it (typed
    here: the catalog lies outside the repository)."""
    return {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
            "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
            "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
            "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
            "vocab_size": 200064}


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_nine_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names)
    # the listless readers read it unasked
    assert {"flash_ms", "flash_roofline", "layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms",
            "layers_rest_ms", "unscoped_pct", "head_loss_ms", "guard_select_ms"} <= set(names)
    assert not {"collective_ms", "moe_ms", "ssd_ms", "ssm_mixer_ms", "window_attn_ms", "mlp_ms", "attn_proj_ms",
                "param_gather_ms"} & set(names)
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs), other["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    scan = "kernels: ops/selective_scan.py"
    for name in READERS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
        assert metric["layer"] == (scan if name.startswith("selscan") else "model: models/base.py")
    assert (by_name["selscan_roofline"]["unit"], by_name["selscan_roofline"]["better"]) == ("%", "higher")
    assert {by_name[n]["source"] for n in ("selscan_state_abs_max", "published_mib")} == {"program_counter"}
    assert cell.chips == 1 and cell.tokens_a_step == 8192
    assert cell.workload["traffic"] == "b1-s8k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1"] and cell.traffic["warmup_steps"] == 6
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"] and len(entry["why"]) <= 200
    assert len(cell.workload["why"]) <= 200


def test_every_width_is_the_published_one_and_reduced_is_depth_and_vocabulary():
    """The catalog's row key for key; the depth and the vocabulary alone are cut, the depth to one
    period of each decoder and the pair that joins them BY PUBLISHED INDEX, the vocabulary to its eighth."""
    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 200064 // 8)
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"Phi-4-mini-flash-reasoning"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    fields = cells.config_fields(config)
    assert fields["layer_indices"] == CUT and len(fields["layer_types"]) == 32 and fields["num_layers"] == 6
    assert [fields["layer_types"][i] for i in CUT] == [
        "mamba1", "sliding_attention", "mamba1", "full_attention", "gmu", "cross_attention"]
    # what flash_roofline reads: the published 64-wide heads, whatever the one call pads them to
    assert (fields["num_heads"], fields["num_kv_heads"], fields["head_dim"]) == (40, 20, 64)
    assert (fields["mamba_d_state"], fields["mamba_d_conv"], fields["mamba_expand"], fields["mamba_dt_rank"]) == (
        16, 4, 2, 160)
    assert fields["tie_embeddings"] is True and fields["position_type"] == "none" and fields["diff_attention"]
    assert config["vocab_size"] * 8 >= want["vocab_size"]
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    # every `assumed` that another reading of the published file could flip names its other candidate
    for key in ("mamba_d_state", "mamba_dt_rank", "attention_bias", "head_pairing", "lambda_init", "sliding_window",
                "memory", "sub_norm"):
        assert {"here", "evidence", "other_candidate"} <= set(config["assumed"][key]), key
    assert "vocab_tp 8" in config["deployment"] and "8192-token" in config["deployment"]
    from galvatron_tpu.models import phi4flash

    assert config["source"] == phi4flash.PHI_4_MINI_FLASH_SOURCE
    preset = phi4flash.PUBLISHED["phi-4-mini-flash-reasoning"]
    assert {k: preset[k] for k in want} == want
    assert config["layer_types"] == phi4flash.layer_types(32)
    assert all(config[k] == v for k, v in phi4flash.ASSUMED.items() if v != "auto")


def test_the_program_built_from_the_file_counts_697_094_272_parameters():
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert cfg.layer_kinds() == ("mamba1.dense", "window.dense", "mamba1.dense", "dense", "gmu.dense", "cross.dense")
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == 697_094_272 + 3  # and the three attention layers' `lambda_init`, no parameters
    assert count * 16 / 2 ** 30 == pytest.approx(10.39, abs=0.01)  # GiB of state, of a chip's 15.75


def test_the_first_loss_is_a_tied_unscaled_heads():
    cell = cells.load_cell(REPO, CELL)
    first = cell.config["checks"]["first_loss"]
    assert harness.expected_first_loss(cell) == pytest.approx(
        math.log(25008) + 2560 * 0.02 ** 2 / 2 + first["plus"], abs=1e-12)
    assert abs(first["plus"]) < 0.05 and first["abs"] <= 0.1 and "tied" in first["plus_why"]
    assert cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    mamba = c.mamba_mixer_fwd_flops_a_token(f)
    assert mamba["projections"] == 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert mamba["core"] == 4 * 5120 * 16  # a multiply-add into the state and one out of it
    full = c.attention_mixer_fwd_flops_a_token(f, 8192, c.FULL)
    assert full["projections"] == 2 * (2560 * 2560 + 2560 * 2560 + 2560 * 2560)
    # two score maps of 64 and two p v products of 128 a pair, 20 pairs, the causal half: 1.5 x an ordinary core
    assert full["core"] == 20 * (2 * 2 * 8192 * 64 + 2 * 2 * 8192 * 128) // 2 == 1.5 * (2 * 2 * 8192 * 2560) / 2
    cross = c.attention_mixer_fwd_flops_a_token(f, 8192, c.CROSS)
    assert cross == {"projections": 2 * 2 * 2560 * 2560, "core": full["core"]}
    window = c.attention_mixer_fwd_flops_a_token(f, 8192, c.WINDOW)
    seen = (512 * 8192 - 512 * 511 / 2) / 8192
    assert window["projections"] == full["projections"] and window["core"] == 3 * 2 * seen * 2560
    gmu, mlp, head = c.gmu_fwd_flops_a_token(f), c.mlp_fwd_flops_a_token(f), 2 * 2560 * 25008
    assert (gmu, mlp) == (2 * 2 * 2560 * 5120, 3 * 2 * 2560 * 10240)
    fwd = (2 * sum(mamba.values()) + sum(window.values()) + sum(full.values()) + gmu + sum(cross.values())
           + 6 * mlp + head)
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(4.583, abs=5e-4)
    assert c.mamba_layers(f) == 2 and c.kinds_run(f)[2:4] == ["mamba1", "full_attention"]
    # a change of sequence length moves the full and the cross cores alone (the band is at its width)
    assert c.train_flops_a_token(f, 16384) - c.train_flops_a_token(f, 8192) == pytest.approx(
        3 * (2 * full["core"] + window["core"] * (c.attention_mixer_fwd_flops_a_token(f, 16384, c.WINDOW)["core"]
                                                   / window["core"] - 1)))
    shares = {"MLPs": 6 * mlp, "Mamba projections": 2 * mamba["projections"], "GMU": gmu,
              "attention projections": window["projections"] + full["projections"] + cross["projections"],
              "full and cross cores": 2 * full["core"], "head": head}
    assert {k: round(100 * v / fwd, 1) for k, v in shares.items()} == {
        "MLPs": 61.8, "Mamba projections": 10.8, "GMU": 3.4, "attention projections": 6.9,
        "full and cross cores": 8.2, "head": 8.4}


def test_the_scans_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.selscan_cost(f, 8192, "fwd"), c.selscan_cost(f, 8192, "bwd")
    assert fwd["flops"] == 4 * 5120 * 16 * 8192 and bwd["flops"] == 2 * fwd["flops"]
    x, dt, bc = 5120 * 2, 5120 * 4, 2 * 16 * 2
    assert fwd["bytes"] == (x + dt + bc + x) * 8192  # each operand in, m out, once
    assert bwd["bytes"] == (x + dt + bc + x + x + dt + bc) * 8192  # those, m's cotangent, and the gradients
    # memory bound at the chip's peaks: 0.41 ms forward, 0.72 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["bytes"] / 819e9, "memory")
    assert flops.least_time_s(fwd, PEAK)[0] * 1e3 == pytest.approx(0.410, abs=2e-3)
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(0.718, abs=2e-3)


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, new_parts=True):
    """The cell's step as the compiled step labels it: six runs of one layer, the program's scope
    names nested under the transforms' wrappers."""
    run = [tracing.layers_scope(k) for k in range(6)]
    fwd = lambda k: "jit(train_step)/jvp(%s)/" % run[k]  # noqa: E731
    bwd = lambda k: "jit(train_step)/transpose(jvp(%s))/checkpoint/" % run[k]  # noqa: E731
    remat = lambda k: bwd(k) + "rematted_computation/"  # noqa: E731
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", fwd(3) + "pallas_call"): [2e-3, 1],  # the full layer's: flash_ms
        label("fusion.5", fwd(3) + tracing.ATTN_PROJ + "/dot_general"): [1e-3, 1],
        label("fusion.6", fwd(1) + tracing.ATTN_WINDOW + "/dot_general"): [1.5e-3, 1],
        label("fusion.7", fwd(0) + tracing.MLP + "/dot_general"): [10e-3, 6],
        label("fusion.8", remat(0) + tracing.MLP + "/dot_general"): [6e-3, 6],
        label("fusion.9", bwd(0) + tracing.MLP + "/dot_general"): [20e-3, 6],
        label("fusion.10", fwd(0) + "add"): [0.5e-3, 6],  # a run's self time
    }
    if new_parts:
        ops.update({
            label("fusion.2", fwd(0) + tracing.ATTN_MAMBA + "/dot_general"): [4e-3, 2],
            label("fusion.3", bwd(2) + tracing.ATTN_MAMBA + "/dot_general"): [8e-3, 2],
            label("fusion.4", fwd(2) + tracing.ATTN_SELSCAN + "/while/body/mul"): [20e-3, 128],
            label("fusion.11", remat(2) + tracing.ATTN_SELSCAN + "/while/body/mul"): [20e-3, 128],
            label("fusion.12", bwd(2) + tracing.ATTN_SELSCAN + "/while/body/while/body/mul"): [50e-3, 128],
            label("fusion.13", fwd(4) + tracing.ATTN_GMU + "/dot_general"): [3e-3, 2],
            label("fusion.14", bwd(5) + tracing.ATTN_DIFF + "/sub"): [2.5e-3, 3],
            label("fusion.15", fwd(5) + tracing.ATTN_CROSS + "/dot_general"): [0.75e-3, 2],
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 10.6, "selscan_state_abs_max": 2.0 + i, "published_mib": 120.0}
        for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("mamba_mixer_ms", run) == pytest.approx(12.0)  # not the scan
    assert read("selscan_ms", run) == pytest.approx(90.0)  # forward, recomputed, backward
    assert read("mamba_mixer_ms", run) + read("selscan_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.(mamba|selscan)"))
    assert read("gmu_ms", run) == pytest.approx(3.0) and read("diff_combine_ms", run) == pytest.approx(2.5)
    assert read("phi4_attn_proj_ms", run) == pytest.approx(1.0 + 1.5 + 0.75)  # full + window + cross
    assert read("phi4_mlp_ms", run) == pytest.approx(36.0) == read("mlp_ms", run)
    assert read("selscan_state_abs_max", run) == pytest.approx(3.5) and read("published_mib", run) == 120.0
    assert read("flash_ms", run) == pytest.approx(2.0)
    assert set(telemetry.SHARED_STEP_FIELDS) == {"selscan_state_abs_max", "published_mib"}
    assert set(telemetry.SHARED_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    # the names the patterns spell are the program's, and none begins another
    names = (tracing.ATTN_MAMBA, tracing.ATTN_SELSCAN, tracing.ATTN_GMU, tracing.ATTN_DIFF, tracing.ATTN_CROSS)
    assert names == ("gt.attn.mamba", "gt.attn.selscan", "gt.attn.gmu", "gt.attn.diff", "gt.attn.cross")
    every = [getattr(tracing, n) for n in dir(tracing) if n.isupper() and isinstance(getattr(tracing, n), str)
             and getattr(tracing, n).startswith("gt.")]
    assert not any(a != b and b.startswith(a) for a in names for b in every)
    # the layer readers see the nested scopes as the layers', and the parts add up
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert parts["rest"] == pytest.approx(0.5) and parts[tracing.ATTN_SELSCAN] == pytest.approx(90.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))


def test_the_share_of_the_floor_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 2 * sum(flops.least_time_s(c.selscan_cost(f, 8192, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(2.257, abs=5e-3)
    run = handmade()
    assert read("selscan_roofline", run) == pytest.approx(100 * least / 90e-3)
    for lab, value in run["trace"]["ops_a_step"].items():
        if "gt.attn.selscan" in lab:
            which = "bwd" if "transpose" in lab and "rematted" not in lab else "fwd"
            value[0] = 2 * flops.least_time_s(c.selscan_cost(f, 8192, which), PEAK)[0]
    assert 50.0 < read("selscan_roofline", run) < 100.0  # a recomputed forward reads under 100
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.selscan" in k and "rematted" in k)}
    assert read("selscan_roofline", run) == pytest.approx(100.0)


def test_a_program_without_the_scopes_or_the_counters_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None, not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    parent = handmade(counters=False, new_parts=False)  # gt.mlp, gt.attn.proj and gt.attn.window, nothing new
    for name in READERS:
        assert (read(name, parent) is None) == (name != "phi4_mlp_ms"), name
    assert read("phi4_mlp_ms", parent) == pytest.approx(36.0)
    granite = {**handmade(), "cell": cells.load_cell(REPO, "granite4h-c1-s4k")}
    assert read("selscan_roofline", granite) is None  # its FLOPs module has no selscan_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("selscan_roofline", dense_cell) is None  # its configuration names no `flops`


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    config["reduced"]["vocab_size"]["here"] = TINY["vocab_size"]
    config["checks"]["first_loss"]["plus"] = 0.0
    (tmp_path / "benchmarks/configs/phi4-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-phi4.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "phi4-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]), "file": "benchmarks/configs/phi4-tiny.json"})
    manifest["workloads"].append({"name": "phi4-tiny-cell", "config": "phi4-tiny",
                                  "traffic": "b2-s128-phi4", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("phi4-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed files'; only the sizes are
    the test's. Everything but the TPU kernel check holds on the CPU: six runs of one layer, two chunks
    of the scan a sequence, what two layers publish read by two others, the tied head."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "phi4-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "phi4-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "phi4-tiny-cell")
    cell = cells.load_cell(root, "phi4-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 57, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

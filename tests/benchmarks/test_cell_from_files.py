"""A cell is added as files and entries: a temporary cell made of three new
files (a configuration, a traffic mix, a per-layer metric) and its entries in
a manifest runs through the harness on the CPU at a tiny size, and no file of
the benchmark is edited for it. The CPU is let in by the test alone (a peak
table of its own handed to `run_cell`); `benchmarks/run.py` refuses it."""

import json
import os
import shutil

import pytest

from benchmarks import cells, harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "source": "test", "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000.0, "initializer_range": 0.02,
    "program": {
        "config_fn": "galvatron_tpu.models.llama:llama_config", "preset": "llama-0.3b",
        "fields": {
            "hidden_size": "$hidden_size", "ffn_hidden": "$intermediate_size",
            "num_heads": "$num_attention_heads", "num_kv_heads": "$num_key_value_heads",
            "head_dim": 16, "num_layers": "$num_hidden_layers", "vocab_size": "$vocab_size",
            "layernorm_eps": "$rms_norm_eps", "rope_theta": "$rope_theta",
            "init_std": "$initializer_range", "qkv_bias": True}},
    "reference": "decoder_lm",
    "checks": {"first_loss": {"abs": 0.1, "why": "test"},
               "reference_loss": {"abs": 0.002, "why": "test"}},
}
TINY_TRAFFIC = {
    1: {"why": "test", "global_batch": 2, "seq_length": 32, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6},
    4: {"why": "test", "global_batch": 4, "seq_length": 32, "chips": 4,
        "train_flags": ["--world_size", "4", "--global_tp_deg", "2", "--default_dp_type", "zero2",
                        "--vocab_tp", "2", "--checkpoint", "1"], "warmup_steps": 6},
}
# what cannot hold off the chip: the TPU kernel, and XLA:CPU's own choice of
# collectives (it has no reduce-scatter)
NOT_ON_THE_CPU = {1: {"kernel_in_step"}, 4: {"kernel_in_step", "layout_collectives"}}
NEW_METRIC = '''def read(run):
    return float(run["window"]["steps"])
'''


@pytest.fixture(params=[1, 4], ids=["one_chip", "tp2_dp2"])
def root(tmp_path, request):
    """A checkout's worth of benchmark: the repo's own files, untouched, plus
    the new cell's three files and a manifest with its entries added."""
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmarks/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (tmp_path / "benchmarks/traffic/b2-s32.json").write_text(json.dumps(TINY_TRAFFIC[request.param]))
    (tmp_path / "benchmarks/layer_metrics/steps_in_window.py").write_text(NEW_METRIC)
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"].append({"name": "tiny", "source": "test", "reduced": [], "why": "test",
                                "file": "benchmarks/configs/tiny.json"})
    manifest["workloads"].append({"name": "tiny-cell", "config": "tiny", "traffic": "b2-s32",
                                  "chips": request.param, "why": "test"})
    manifest["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry: cli/train.py host loop",
        "moves": "tokens_per_s_chip", "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_a_cell_added_as_files_runs(root, tmp_path):
    cell = cells.load_cell(root, "tiny-cell")
    assert cell.fields["num_kv_heads"] == 2 and cell.fields["rope_theta"] == 1e6
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "steps_in_window" in names and "collective_ms" not in names
    lines = []
    result = harness.run_cell(
        cell, seed=2**31 + 77, seconds=0.5, traced=False,
        peaks={"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
        t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    # everything but the TPU kernel check holds on the CPU
    assert {k for k, ok in detail["checks"].items() if not ok} == NOT_ON_THE_CPU[cell.chips]
    assert ("params_span_all_chips" in detail["checks"]) == (cell.chips == 4)
    assert result["correct"] is False
    assert abs(detail["first_loss"] - detail["reference_loss"]) < 0.002
    w = detail["window"]
    assert w["steps"] >= 1 and w["window_s"] >= 0.5
    assert result["attempted"] == w["steps"] and result["failed"] == 0
    # all the window's steps over all its time
    assert result["metrics"]["tokens_per_s_chip"]["value"] == pytest.approx(
        w["steps"] * cell.tokens_a_step / w["window_s"] / cell.chips)
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    # the plain reference is the benchmark's own work: timed, and not set-up
    assert detail["reference_s"] > 0 and "reference_s" not in detail["setup_parts_s"]
    assert sum(detail["setup_parts_s"].values()) == pytest.approx(detail["setup_s"])
    saved = json.load(open(os.path.join(str(tmp_path), "run.json")))
    assert len(saved["intervals_s"]) == result["attempted"]

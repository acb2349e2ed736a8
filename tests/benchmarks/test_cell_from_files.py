"""A cell is added as files and entries: temporary cells made of new files (a
configuration, a traffic mix, a per-layer metric; for an architecture the
benchmark has not seen, its own plain reference and its own count of model
FLOPs too) and their entries in a manifest run through the harness on the CPU
at a tiny size, and no file of the benchmark is edited for them. The CPU is
let in by the test alone (a peak table of its own handed to `run_cell`);
`benchmarks/run.py` refuses it."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness

from . import test_manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_CONFIG = {
    "source": "https://example.invalid/tiny/config.json",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000.0, "initializer_range": 0.02,
    "reduced": {},
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

# A third architecture: neither of the two families `test_manifest.HELD_TO`
# knows by name, and switches that fall into neither of their branches (the
# tanh GELU, an untied head, no MLP biases, heads of 16). It brings its own
# reference, its own FLOPs module, what its objective adds to the first loss
# (`plus`) and, on four chips, the collectives of its layout.
THIRD_PLUS = 0.03
THIRD_CONFIG = {
    "source": "https://example.invalid/third/config.json",
    "architectures": ["ThirdForCausalLM"],
    "d_model": 64, "d_ff": 128, "heads": 4, "depth": 2, "vocab": 512, "positions": 48,
    "eps": 1e-5, "init": 0.02,
    "reduced": {"depth": {"published": 16, "here": 2, "why": "test"}},
    "program": {
        "config_fn": "galvatron_tpu.models.gpt:gpt_config", "preset": "gpt-0.3b",
        "fields": {
            "hidden_size": "$d_model", "ffn_hidden": "$d_ff", "num_heads": "$heads",
            "head_dim": 16, "num_layers": "$depth", "vocab_size": "$vocab",
            "max_seq_len": "$positions", "layernorm_eps": "$eps", "init_std": "$init",
            "activation": "gelu", "tie_embeddings": False, "mlp_bias": False}},
    "reference": "third_lm",
    "flops": "third_lm",
    "checks": {"first_loss": {"abs": 0.1, "why": "test", "plus": THIRD_PLUS,
                              "plus_why": "test: stands for a router's losses at initialisation"},
               "reference_loss": {"abs": 0.002, "why": "test"}},
}
THIRD_TRAFFIC = {
    1: {"why": "test", "global_batch": 2, "seq_length": 48, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "0"], "warmup_steps": 6},
    # what XLA:CPU does emit under tp2 x dp2 with ZeRO-2
    4: {"why": "test", "global_batch": 4, "seq_length": 48, "chips": 4,
        "train_flags": ["--world_size", "4", "--global_tp_deg", "2", "--default_dp_type", "zero2",
                        "--checkpoint", "0"], "warmup_steps": 6,
        "collectives": ["all-reduce", "all-gather"]},
}
# written for this block alone, sharing no line with references/decoder_lm.py:
# a batch at a time and all heads at once
THIRD_REFERENCE = '''import jax
import jax.numpy as jnp


def _layernorm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(((x - mean) ** 2).mean(-1, keepdims=True) + eps) * p["scale"] + p["bias"]


def loss(params, batch, fields):
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        eps, seq = fields["layernorm_eps"], batch["tokens"].shape[1]
        x = params["embed"]["wte"][batch["tokens"]] + params["embed"]["wpe"][batch["positions"]]
        for lp in params["layers"]:
            qkv = jnp.einsum("bsh,hcnd->cbnsd", _layernorm(x, lp["ln1"], eps), lp["wqkv"]["kernel"])
            q, k, v = qkv + lp["wqkv"]["bias"][:, None, :, None, :]
            scores = jnp.einsum("bnsd,bntd->bnst", q, k) / q.shape[-1] ** 0.5
            scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
            heads = jnp.einsum("bnst,bntd->bsnd", jax.nn.softmax(scores, -1), v)
            x = x + heads.reshape(x.shape[:2] + (-1,)) @ lp["wo"]["kernel"] + lp["wo"]["bias"]
            mid = jax.nn.gelu(_layernorm(x, lp["ln2"], eps) @ lp["wi"]["kernel"], approximate=True)
            x = x + mid @ lp["wo_mlp"]["kernel"]
        logits = _layernorm(x, params["final_norm"], eps) @ params["lm_head"]["kernel"]
        nll = -jnp.take_along_axis(jax.nn.log_softmax(logits), batch["labels"][..., None], -1)[..., 0]
        mask = batch["loss_mask"].astype(jnp.float32)
        return (nll * mask).sum() / mask.sum()
'''
# the dense block's matmuls and, standing for what a new block adds, a router
# of 8 columns: the count is this file's and not benchmarks/flops.py's
THIRD_FLOPS = '''def train_flops_a_token(fields, seq_len):
    hidden, ffn = fields["hidden_size"], fields["ffn_hidden"]
    width = fields["num_heads"] * fields["head_dim"]
    layer = 2.0 * hidden * 3 * width + 2.0 * seq_len * width + 2.0 * width * hidden \\
        + 4.0 * hidden * ffn + 2.0 * hidden * 8
    return 3.0 * (fields["num_layers"] * layer + 2.0 * hidden * fields["vocab_size"])
'''
THIRD_ROUTER_FLOPS = 3.0 * 2 * (2.0 * 64 * 8)  # fwd + bwd, 2 layers
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


@pytest.fixture(params=[1, 4], ids=["one_chip", "tp2_dp2"])
def root(tmp_path, request):
    """A checkout's worth of benchmark: the repo's own files, untouched, plus
    the new cells' files and a manifest with their entries added."""
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(tmp_path / "benchmarks/model_flops", exist_ok=True)
    for path, text in [
            ("configs/tiny.json", json.dumps(TINY_CONFIG)),
            ("traffic/b2-s32.json", json.dumps(TINY_TRAFFIC[request.param])),
            ("layer_metrics/steps_in_window.py", NEW_METRIC),
            ("configs/third.json", json.dumps(THIRD_CONFIG)),
            ("traffic/b2-s48-third.json", json.dumps(THIRD_TRAFFIC[request.param])),
            ("references/third_lm.py", THIRD_REFERENCE),
            ("model_flops/third_lm.py", THIRD_FLOPS)]:
        (tmp_path / "benchmarks" / path).write_text(text)
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"] += [
        {"name": "tiny", "source": "test", "reduced": [], "why": "test",
         "file": "benchmarks/configs/tiny.json"},
        {"name": "third", "source": "test", "reduced": ["depth"], "why": "test",
         "file": "benchmarks/configs/third.json"}]
    manifest["workloads"] += [
        {"name": "tiny-cell", "config": "tiny", "traffic": "b2-s32",
         "chips": request.param, "why": "test"},
        {"name": "third-cell", "config": "third", "traffic": "b2-s48-third",
         "chips": request.param, "why": "test"}]
    manifest["per_layer"].append({
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry: cli/train.py host loop",
        "moves": "tokens_per_s_chip", "workloads": ["tiny-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def run(cell, out_dir, seed):
    lines = []
    result = harness.run_cell(cell, seed=seed, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=out_dir, say=lambda **o: lines.append(o))
    return result, lines[-1]


def test_a_cell_added_as_files_runs(root, tmp_path):
    cell = cells.load_cell(root, "tiny-cell")
    assert cell.fields["num_kv_heads"] == 2 and cell.fields["rope_theta"] == 1e6
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "steps_in_window" in names and "collective_ms" not in names
    result, detail = run(cell, str(tmp_path), 2**31 + 77)
    # everything but the TPU kernel check holds on the CPU
    assert {k for k, ok in detail["checks"].items() if not ok} == NOT_ON_THE_CPU[cell.chips]
    assert ("params_span_all_chips" in detail["checks"]) == (cell.chips == 4)
    assert result["correct"] is False
    assert abs(detail["first_loss"] - detail["reference_loss"]) < 0.002
    # no `flops`, `plus` or `collectives` in its files: the defaults
    assert detail["flops_a_token"] == flops.train_flops_a_token(cell.fields, 32)
    assert detail["expected_first_loss"] == math.log(512) + 64 * 0.02 ** 2 / 2
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


def test_a_third_architecture_added_as_files_runs(root, tmp_path):
    """The case a `model_config` PR is in: the reference, the FLOPs, the first
    loss's constant and the layout's collectives are the cell's own files'."""
    for held in ("references", "model_flops"):  # the repo has neither module
        assert not os.path.exists(os.path.join(REPO, "benchmarks", held, "third_lm.py"))
    cell = cells.load_cell(root, "third-cell")
    assert cell.workload["config"] not in test_manifest.HELD_TO
    result, detail = run(cell, str(tmp_path), 2**31 + 79)
    # on four chips the mix's own `collectives` are what the step is held to,
    # so the check that the default triple fails on XLA:CPU passes here
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert ("layout_collectives" in detail["checks"]) == (cell.chips == 4)
    assert cell.collectives == (("all-reduce", "all-gather") if cell.chips == 4
                                else cells.DEFAULT_COLLECTIVES)
    # its own reference agrees with the program on the seed's weights and batch
    assert abs(detail["first_loss"] - detail["reference_loss"]) < 0.002
    # its own FLOPs: the dense count and the module's router term, and the MFU from it
    dense = flops.train_flops_a_token(cell.fields, 48)
    assert detail["flops_a_token"] == pytest.approx(dense + THIRD_ROUTER_FLOPS, rel=1e-12)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["mfu"] == pytest.approx(
        100 * metrics["tokens_per_s_chip"] * detail["flops_a_token"] / 1e12)
    # what its objective adds to the cross entropy at initialisation
    assert detail["expected_first_loss"] == pytest.approx(
        math.log(512) + 64 * 0.02 ** 2 / 2 + THIRD_PLUS, abs=1e-12)
    assert set(metrics) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}


def test_the_temporary_manifest_passes_the_manifest_wide_tests(root):
    manifest = cells.load_json(root, cells.MANIFEST)
    assert [w["name"] for w in manifest["workloads"]][-2:] == ["tiny-cell", "third-cell"]
    for workload in manifest["workloads"]:
        test_manifest.check_cell_finds_its_files(root, workload["name"])
        test_manifest.check_the_program_receives_the_published_keys(root, workload["name"])
    for config in manifest["configs"]:
        test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, config["name"])


def test_a_cut_that_cuts_nothing_is_refused(root):
    path = os.path.join(root, "benchmarks", "configs", "third.json")
    for cut in ({"published": 2, "here": 2}, {"published": 16, "here": 4}):
        config = json.loads(json.dumps(THIRD_CONFIG))
        config["reduced"]["depth"].update(cut)
        json.dump(config, open(path, "w"))
        with pytest.raises(AssertionError):
            test_manifest.check_the_program_receives_the_published_keys(root, "third-cell")


def test_plus_is_added_to_the_expected_first_loss(root):
    cross_entropy = math.log(512) + 64 * 0.02 ** 2 / 2
    assert harness.expected_first_loss(cells.load_cell(root, "tiny-cell")) == cross_entropy
    assert harness.expected_first_loss(cells.load_cell(root, "third-cell")) == \
        cross_entropy + THIRD_PLUS
    # OLMoE's: load balancing 0.01 x 8 at a uniform router, z-loss 0.001 x (ln 64)^2
    path = os.path.join(root, "benchmarks", "configs", "third.json")
    config = json.loads(json.dumps(THIRD_CONFIG))
    config["checks"]["first_loss"]["plus"] = 0.01 * 8 + 0.001 * math.log(64) ** 2
    json.dump(config, open(path, "w"))
    assert harness.expected_first_loss(cells.load_cell(root, "third-cell")) == pytest.approx(
        cross_entropy + 0.0973, abs=5e-5)

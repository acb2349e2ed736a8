"""BENCHMARK.json against the files it names, and each configuration as the
program receives it. The three manifest-wide checks take a root, so that
test_cell_from_files.py holds a temporary manifest to them too."""

import json
import os

import jax.numpy as jnp
import pytest

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


def qwen25_7b(cfg):
    assert cfg.head_dim == 128
    # carried, not defaulted away by llama_config
    assert cfg.qkv_bias and not cfg.out_bias and cfg.rope_theta == 1e6
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.ffn_hidden) == (28, 4, 18944)


def cerebras_gpt_67b(cfg):
    assert cfg.head_dim == 128
    assert cfg.activation == "gelu_exact" and cfg.tie_embeddings and cfg.mlp_bias


# what a configuration is held to beyond what its own file says, by the
# configuration's name; a new configuration needs no row
HELD_TO = {
    "qwen2.5-7b-d2-v4": qwen25_7b,
    "qwen2.5-7b-d4": qwen25_7b,
    "cerebras-gpt-6.7b-d2": cerebras_gpt_67b,
}


def check_cell_finds_its_files(root, workload):
    cell = cells.load_cell(root, workload)
    assert cell.chips in (1, 4) and cell.tokens_a_step > 0
    assert cell.config["source"].startswith("https://")
    for key in cell.config["reduced"]:
        assert key in cell.config, key
    ref = cells.load_module(root, "benchmarks/references/%s.py" % cell.config["reference"])
    assert callable(ref.loss)
    assert cells.flops_a_token(cell) > 0
    first_loss = cell.config["checks"]["first_loss"]
    assert ("plus" in first_loss) == ("plus_why" in first_loss)
    assert cell.collectives and all(isinstance(c, str) for c in cell.collectives)
    for metric in cell.metrics("per_layer"):
        reader = cells.load_module(root, "benchmarks/layer_metrics/%s.py" % metric["name"])
        assert callable(reader.read)
        assert metric["moves"] in [m["name"] for m in cell.metrics("end_to_end")]


def check_reduced_in_the_manifest_is_reduced_in_the_file(root, config):
    manifest = cells.load_json(root, cells.MANIFEST)
    entry = next(c for c in manifest["configs"] if c["name"] == config)
    assert sorted(entry["reduced"]) == sorted(cells.load_json(root, entry["file"])["reduced"])


def check_the_program_receives_the_published_keys(root, workload):
    cell = cells.load_cell(root, workload)
    fam = cells.register_family(cell)
    cfg = fam.config_fn(fam.default_size, max_seq_len=cell.traffic["seq_length"],
                        compute_dtype=jnp.bfloat16)
    for field, value in cell.fields.items():
        assert getattr(cfg, field) == value, field
    assert cfg.max_seq_len == cell.traffic["seq_length"]
    # a cut is a cut, and the file runs what it says it runs
    for key, cut in cell.config["reduced"].items():
        assert cut["published"] != cut["here"] and cut["here"] == cell.config[key], key
    HELD_TO.get(cell.workload["config"], lambda cfg: None)(cfg)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_finds_its_files(workload):
    check_cell_finds_its_files(REPO, workload)


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_reduced_in_the_manifest_is_reduced_in_the_file(config):
    check_reduced_in_the_manifest_is_reduced_in_the_file(REPO, config)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_program_receives_the_published_keys(workload):
    check_the_program_receives_the_published_keys(REPO, workload)


def test_a_missing_cell_or_file_is_an_error():
    with pytest.raises(cells.CellError):
        cells.load_cell(REPO, "no-such-cell")
    with pytest.raises(cells.CellError):
        cells.load_json(REPO, "benchmarks/configs/no-such.json")
    with pytest.raises(cells.CellError):
        cells.config_fields({"program": {"fields": {"hidden_size": "$absent"}}})


def test_seeds_beyond_31_bits_reach_the_trainer_folded():
    cell = cells.load_cell(REPO, WORKLOADS[0])
    argv = cells.train_argv(cell, 2**31 + 12345)
    assert argv[argv.index("--seed") + 1] == "12345"
    assert "--xla_trace" not in argv
    traced = cells.train_argv(cell, 1, "/x", (6, 8))
    assert traced[-4:] == ["--xla_trace", "/x", "--trace_steps", "6:8"]

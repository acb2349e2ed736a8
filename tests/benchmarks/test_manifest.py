"""BENCHMARK.json against the files it names, and each configuration as the
program receives it."""

import json
import os

import jax.numpy as jnp
import pytest

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_finds_its_files(workload):
    cell = cells.load_cell(REPO, workload)
    assert cell.chips in (1, 4) and cell.tokens_a_step > 0
    assert cell.config["source"].startswith("https://")
    for key in cell.config["reduced"]:
        assert key in cell.config, key
    ref = cells.load_module(REPO, "benchmarks/references/%s.py" % cell.config["reference"])
    assert callable(ref.loss)
    for metric in cell.metrics("per_layer"):
        reader = cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % metric["name"])
        assert callable(reader.read)
        assert metric["moves"] in [m["name"] for m in cell.metrics("end_to_end")]


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_reduced_in_the_manifest_is_reduced_in_the_file(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    assert sorted(entry["reduced"]) == sorted(cells.load_json(REPO, entry["file"])["reduced"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_the_program_receives_the_published_keys(workload):
    cell = cells.load_cell(REPO, workload)
    fam = cells.register_family(cell)
    cfg = fam.config_fn(fam.default_size, max_seq_len=cell.traffic["seq_length"],
                        compute_dtype=jnp.bfloat16)
    for field, value in cell.fields.items():
        assert getattr(cfg, field) == value, field
    assert cfg.head_dim == 128 and cfg.max_seq_len == cell.traffic["seq_length"]
    if "Qwen2ForCausalLM" in cell.config["architectures"]:
        # carried, not defaulted away by llama_config
        assert cfg.qkv_bias and not cfg.out_bias and cfg.rope_theta == 1e6
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.ffn_hidden) == (28, 4, 18944)
    else:
        assert cfg.activation == "gelu_exact" and cfg.tie_embeddings and cfg.mlp_bias


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

"""The Nemotron-3-Nano cell `nemo3n-c1-s8k`: its configuration against the catalog's row, its files through the
harness on the CPU at a tiny size, its FLOPs and its two kernels' floors by hand arithmetic, and THE ACCEPTED
READERS on a temporary manifest with the cell appended to their lists, over one step recorded on the chip.
`per_layer` is full (128 of 128), so this PR adds no entry and no per-family copy of a reader: the accepted
readers take their costs from the configuration's own `model_flops` module, and `WAITING` below is the list of
entries the cell joins the day a `benchmark` PR makes room (PERF.md section 7). Every assertion is by NAME."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.models import nemotron_h

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
CELL = "nemo3n-c1-s8k"
CONFIG = "nemotron-3-nano-30b-a3b-d9-e8-v8"
# the accepted entries whose `workloads` list takes the cell
WAITING = ("ssd_ms", "ssd_roofline", "ssm_mixer_ms", "ssm_state_abs_max", "moe_held_ms", "moe_held_dispatch_ms",
           "moe_held_experts_ms", "moe_held_gmm_roofline", "moe_held_load_max_over_mean", "moe_rows_held_over_even",
           "moe_shared_ms", "flash_ms", "flash_roofline", "attn_proj_ms")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the published file with every size made small; the pattern, the switches, the reference, the FLOPs module and
# the checks are the file's own
TINY = {"hidden_size": 64, "moe_intermediate_size": 32, "intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 48, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 2, "router_width": 8,
        "n_routed_experts": 2, "num_experts_per_tok": 2, "vocab_size": 512, "num_hidden_layers": 9,
        "max_position_embeddings": 128}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/nemotron_h.py")


def published():
    """The catalog's row for NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, as ISSUE 71 quotes it (typed here: the catalog
    lies outside the repository)."""
    return {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
        "n_group": 1, "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


# ------------------------------------------------------- the manifest's side
def test_the_cell_is_named_as_issued_and_joins_no_list_while_per_layer_is_full():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    assert cell.chips == 1 and cell.tokens_a_step == 8192
    assert cell.workload["traffic"] == "b1-s8k-lrw2k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1", "--lr_warmup_iters", "2000"]
    assert len(cell.workload["why"]) <= 200 and "6144" in cell.workload["why"]
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cell.config["source"] == nemotron_h.NEMOTRON_3_NANO_SOURCE
    assert [w["name"] for w in manifest["workloads"]].index(CELL) >= 17  # appended after the seventeen it found
    names = [m["name"] for m in cell.metrics("per_layer")]
    # the entries without a list read the cell from the first run
    assert {"layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms", "layers_rest_ms", "head_loss_ms", "unscoped_pct",
            "device_idle_pct", "optimizer_ms"} <= set(names)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    waiting = [name for name in WAITING if "workloads" in by_name[name]]
    assert waiting == list(WAITING)  # every one of them names its cells
    if len(manifest["per_layer"]) >= 128:  # full: the driver refuses one more, and a list may only grow in a PR of its own
        assert not set(waiting) & set(names)
    else:
        assert set(waiting) <= set(names)


def test_every_width_is_the_published_one_and_reduced_is_depth_experts_and_vocabulary():
    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (9, 8, 131072 // 8)
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    # the pattern stays whole, verbatim, and the two lists are the pattern spelled out
    assert config["hybrid_override_pattern"] == PATTERN
    assert (config["layer_types"], config["mlp_types"]) == tuple(nemotron_h.pattern_layers(PATTERN))
    fields = cells.config_fields(config)
    assert fields["num_layers"] == 9 and "".join(
        {"mamba": "M", "attention": "*", "none": "E"}[t] for t in fields["layer_types"][:9]) == "MEMEM*EME"
    assert (fields["num_heads"], fields["num_kv_heads"], fields["head_dim"]) == (32, 2, 128)
    assert (fields["ssm_num_heads"], fields["ssm_head_dim"], fields["ssm_state_dim"], fields["ssm_conv_kernel"],
            fields["ssm_groups"]) == (64, 64, 128, 4, 8)
    assert (fields["num_experts"], fields["experts_held"], fields["experts_per_token"], fields["ffn_hidden"],
            fields["shared_expert_ffn"], fields["routed_scaling_factor"]) == (128, 8, 6, 1856, 3712, 2.5)
    assert fields["activation"] == "relu2" and fields["position_type"] == "none" and fields["tie_embeddings"] is False
    # the guide's floors: a whole period (nine blocks, every kind), 8 experts, an eighth of the vocabulary
    assert PATTERN[:9] == "MEMEM*EME" and {c: PATTERN[:9].count(c) for c in "ME*"} == {"M": 4, "E": 4, "*": 1}
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    assert {"d_inner", "attention_positions", "initializer_range", "mamba_init", "router_bias_update_rate"} <= set(config["assumed"])
    assert "rope_theta, partial_rotary_factor" in config["not_modelled"]
    assert "expert parallel 16" in config["deployment"] and "6144" in config["deployment"]
    preset = nemotron_h.PUBLISHED["nemotron-3-nano-30b-a3b"]
    assert {k: preset[k] for k in want} == want
    assert config["initializer_range"] == nemotron_h.INITIALIZER_RANGE
    assert config["router_bias_update_rate"] == nemotron_h.ROUTER_BIAS_UPDATE_RATE


def test_the_program_built_from_the_file_counts_666_963_456_parameters():
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=8192)
    assert cfg.layer_kinds() == ("ssm.none", "none.routed", "ssm.none", "none.routed", "ssm.none", "none",
                                 "none.routed", "ssm.none", "none.routed")
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == 666_963_456
    assert count * 16 / 2 ** 30 == pytest.approx(9.94, abs=0.01)  # GiB of state, of a chip's 15.75
    assert harness.expected_first_loss(cell) == pytest.approx(math.log(16384) + 2688 * 0.02 ** 2 / 2, abs=1e-12)
    assert harness.expected_first_loss(cell) == pytest.approx(10.242, abs=1e-3)
    assert "plus" not in cell.config["checks"]["first_loss"]  # the objective is the cross entropy alone
    assert cell.config["checks"]["first_loss"]["abs"] <= 0.1 and cell.config["checks"]["reference_loss"]["abs"] <= 2e-3


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_blocks_of_one_half_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    ssm = c.ssm_mixer_fwd_flops_a_token(f)
    assert ssm["projections"] == 2 * (2688 * (4096 + 4096 + 2 * 8 * 128 + 64) + 4096 * 2688)
    assert ssm["core"] == 4 * 64 * 64 * 128
    attention = c.attention_mixer_fwd_flops_a_token(f, 8192)
    assert attention["projections"] == 2 * (2 * 2688 * 4096 + 2 * 2688 * 256)
    assert attention["core"] == 2 * 8192 * 32 * (128 + 128) // 2
    routed = c.routed_block_fwd_flops_a_token(f)
    assert routed == {"router": 2 * 2688 * 128, "shared": 2 * 2 * 2688 * 3712, "held": 6 * 8 / 128 * 2 * 2 * 2688 * 1856}
    head = 2 * 2688 * 16384
    fwd = 4 * sum(ssm.values()) + sum(attention.values()) + 4 * sum(routed.values()) + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 8192)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(2.137, abs=5e-4)
    assert (c.ssm_layers(f), c.routed_blocks(f)) == (4, 4)
    shares = {"M": 4 * sum(ssm.values()), "E": 4 * sum(routed.values()), "*": sum(attention.values()), "head": head}
    assert {k: round(100 * v / fwd, 1) for k, v in shares.items()} == {"M": 44.6, "E": 27.0, "*": 16.0, "head": 12.4}
    # the whole pattern counts its 23 : 23 : 6
    whole = {**f, "num_layers": 52}
    assert (c.ssm_layers(whole), c.routed_blocks(whole)) == (23, 23)


def test_the_grouped_scans_and_the_two_matrix_experts_floors_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.ssd_cost(f, 8192, "fwd"), c.ssd_cost(f, 8192, "bwd")
    assert fwd["flops"] == 4 * 64 * 64 * 128 * 8192 and bwd["flops"] == 2 * fwd["flops"]
    xbc, y, dt = (64 * 64 + 2 * 8 * 128) * 2, 64 * 64 * 2, 64 * 4  # B and C BY GROUP
    assert fwd["bytes"] == (xbc + dt + y) * 8192 and bwd["bytes"] == (xbc + dt + y + xbc + dt) * 8192
    assert flops.least_time_s(fwd, PEAK)[1] == "memory"
    assert c.gmm_dims(f, "in") == (2688, 1856) and c.gmm_dims(f, "out") == (1856, 2688)  # no gate beside the up projection
    rows = 8192 * 6 * 8 / 128  # the even share, a block
    for kind in ("in", "out"):
        cost = c.gmm_cost(f, kind, rows)
        assert cost["flops"] == 2 * rows * 2688 * 1856
        assert cost["bytes"] == 2 * (2688 * 1856 + rows * 2688 + rows * 1856)
    assert c.gmm_cost(f, "in", 0)["bytes"] == 0.0


# --------------------------------------------- the accepted readers, on a temporary manifest
def appended(manifest, cell_name):
    """The manifest with the cell appended to the lists it waits for, as the `benchmark` PR that makes room
    will append it: nothing else changed."""
    manifest = json.loads(json.dumps(manifest))
    for metric in manifest["per_layer"]:
        if metric["name"] in WAITING and "workloads" in metric:
            metric["workloads"].append(cell_name)
    return manifest


@pytest.fixture(scope="module")
def recorded_run(tmp_path_factory):
    """One step of the traced tail of a `--trace 2` run of the cell on a v5e (my chip run, PR 71), its `step`
    event's counters, and the cell loaded from a root whose manifest has it on the accepted lists."""
    root = tmp_path_factory.mktemp("appended")
    os.symlink(os.path.join(REPO, "benchmarks"), root / "benchmarks")
    (root / "BENCHMARK.json").write_text(json.dumps(appended(cells.load_json(REPO, cells.MANIFEST), CELL)))
    reduced = trace.reduce(trace.load_events(os.path.join(FIXTURES, CELL + ".trace_events.json.gz")),
                           harness.STEP_NAMES)
    with open(os.path.join(FIXTURES, CELL + ".expected.json")) as f:
        expected = json.load(f)
    events = [{"type": "step", "iter": 0, **expected["step_event"]}]
    cell = cells.load_cell(str(root), CELL)
    return {"trace": reduced, "peak": PEAK, "cell": cell, "events": events, "window_steps": (0, 1),
            "summary": {}}, expected


def test_the_accepted_readers_return_a_number_for_the_cell(recorded_run):
    run, expected = recorded_run
    assert run["trace"]["steps"] == expected["steps"] == 1
    listed = {m["name"] for m in run["cell"].metrics("per_layer")}
    assert set(WAITING) <= listed
    values = {}
    for name in WAITING:  # each through the file the harness would load for a cell on its list (`per_layer_values`)
        values[name] = cells.load_module(run["cell"].root, "benchmarks/layer_metrics/%s.py" % name).read(run)
        assert values[name] is not None and values[name] > 0, name
        assert values[name] == pytest.approx(expected[name], rel=1e-9), name
        assert values[name] == pytest.approx(expected["run_reported"][name], rel=0.03), name
    # shares of a floor cannot pass 100
    for name in ("ssd_roofline", "moe_held_gmm_roofline", "flash_roofline"):
        assert 0 < values[name] < 100, name
    # the grouped scan's and the two-matrix experts' costs are THIS configuration's: 4 scans BY GROUP, two calls a kind
    c, f = costs(), run["cell"].fields
    least = 4 * sum(flops.least_time_s(c.ssd_cost(f, 8192, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert values["ssd_roofline"] == pytest.approx(100 * least * 1e3 / values["ssd_ms"], rel=1e-9)
    kernels = cells.load_module(REPO, "benchmarks/layer_metrics/flash_ms.py").per_kernel(run)
    assert sum(calls for _, calls in kernels.values()) > 0 or values["flash_ms"] > 0


def test_the_recorded_steps_parts_add_up(recorded_run):
    """Every nested scope + the runs' self time = forward + recomputation + backward, and with the top-level
    scopes and the unscoped ops the device's busy time: no op is counted twice or dropped, and an absent half
    opened no scope (there is no `gt.mlp` anywhere in the step)."""
    run, expected = recorded_run
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert "gt.mlp" not in parts and {"gt.attn.ssm", "gt.attn.ssd", "gt.attn.proj", "gt.moe.shared", "gt.moe.experts"} <= set(parts)
    layers = sum(read("layers_%s_ms" % phase, run) for phase in ("fwd", "remat", "bwd"))
    assert sum(parts.values()) == pytest.approx(layers, abs=1e-6)
    top = sum(read(name, run) for name in ("embed_ms", "head_loss_ms", "optimizer_ms", "guard_select_ms"))
    unscoped = scopes.ms_a_step(run, scopes.UNSCOPED)
    assert layers + top + unscoped == pytest.approx(run["trace"]["busy_s"] * 1e3, rel=1e-6)
    # 4.67 %: the scans' loops' copies, which XLA names without a scope (PERF.md section 5; Granite's cell reads 4.71)
    assert read("unscoped_pct", run) == pytest.approx(expected["unscoped_pct"], rel=1e-9) and 4.0 < read("unscoped_pct", run) < 5.0


def test_the_parents_program_gives_the_readers_nothing():
    """What a program without these scopes and counters hands the readers: None, not zero and not an error."""
    cell = cells.load_cell(REPO, CELL)
    bare = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}, "peak": PEAK, "cell": cell,
            "events": [], "window_steps": (0, 1), "summary": {}}
    for name in WAITING:
        assert not read(name, bare), name
    assert read("ssd_roofline", {**bare, "trace": None}) is None


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    (tmp_path / "benchmarks/configs/nemo-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-nemo.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1", "--lr_warmup_iters", "2000"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "nemo-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]), "file": "benchmarks/configs/nemo-tiny.json"})
    manifest["workloads"].append({"name": "nemo-tiny-cell", "config": "nemo-tiny", "traffic": "b2-s128-nemo",
                                  "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(appended(manifest, "nemo-tiny-cell")))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed files'; only the sizes are the
    test's. Everything but the TPU kernel check holds on the CPU: nine layers of one half, two groups of B and C,
    2 of 8 experts held, the untied head."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "nemo-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "nemo-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "nemo-tiny-cell")
    cell = cells.load_cell(root, "nemo-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 71, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

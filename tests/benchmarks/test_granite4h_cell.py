"""The Granite-4.0-H cell: its configuration against the catalog's row, its
files through the harness on the CPU at a tiny size, its readers on handmade
labels and events and on one step recorded on the chip, and its FLOPs and the
scan's floor by hand arithmetic. Every assertion is by NAME: none by a
position in `per_layer` or by the count of cells."""

import json
import math
import os
import shutil

import pytest

from benchmarks import cells, flops, harness, scopes, trace
from galvatron_tpu.obs import telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
CELL = "granite4h-c1-s4k"
CONFIG = "granite-4.0-h-micro-d10-v8"
READERS = ("ssm_mixer_ms", "ssd_ms", "ssd_roofline", "ssm_state_abs_max", "g4h_mlp_ms")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the published file with every size made small; the pattern, the multipliers,
# the switches, the reference, the FLOPs module and the checks are the file's own
TINY = {"hidden_size": 64, "shared_intermediate_size": 96, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 10,
        "mamba_n_heads": 4, "mamba_d_head": 32, "mamba_d_state": 16, "vocab_size": 512,
        "max_position_embeddings": 128}
CPU_PEAK = {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def costs():
    return cells.load_module(REPO, "benchmarks/model_flops/granite_hybrid.py")


def published():
    """The catalog's row for granite-4.0-h-micro, as ISSUE 39 quotes it (typed
    here: the catalog lies outside the repository)."""
    pattern = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]
    return {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192, "layer_types": pattern,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True, "vocab_size": 100352}


# ------------------------------------------------------- the manifest's side
def test_the_cell_reports_its_five_metrics_and_the_others_do_not():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    cell = cells.load_cell(REPO, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert set(READERS) <= set(names)
    # the listless readers read it unasked
    assert {"flash_ms", "flash_roofline", "layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms",
            "layers_rest_ms", "unscoped_pct", "head_loss_ms", "guard_select_ms"} <= set(names)
    assert not {"collective_ms", "moe_ms", "moe_held_ms", "latent_attn_ms", "mtp_ms", "param_gather_ms",
                "linear_attn_ms", "delta_rule_ms", "mlp_ms", "mlp_roofline"} & set(names)
    for other in manifest["workloads"]:
        if other["name"] != CELL:
            theirs = [m["name"] for m in cells.load_cell(REPO, other["name"]).metrics("per_layer")]
            assert not set(READERS) & set(theirs), other["name"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    layers = {"ssm_mixer_ms": "model: models/base.py", "g4h_mlp_ms": "model: models/base.py",
              "ssd_ms": "kernels: ops/ssd.py", "ssd_roofline": "kernels: ops/ssd.py",
              "ssm_state_abs_max": "kernels: ops/ssd.py"}
    for name in READERS:
        metric = by_name[name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "tokens_per_s_chip"
        assert metric["layer"] == layers[name]
    assert (by_name["ssd_roofline"]["unit"], by_name["ssd_roofline"]["better"]) == ("%", "higher")
    assert by_name["ssm_state_abs_max"]["source"] == "program_counter"
    assert cell.chips == 1 and cell.tokens_a_step == 4096
    assert cell.workload["traffic"] == "b1-s4k" and cell.workload["config"] == CONFIG
    assert cell.traffic["train_flags"] == ["--checkpoint", "1"] and cell.traffic["warmup_steps"] == 6
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(cell.workload["why"]) <= 200


def test_every_width_is_the_published_one_and_reduced_is_depth_and_vocabulary():
    """The catalog's row for granite-4.0-h-micro, key for key; the depth and
    the vocabulary alone are cut, to the guide's floors, and `layer_types`
    stays whole: the program runs its first ten entries."""
    want = published()
    config = cells.load_cell(REPO, CELL).config
    differs = {k for k, v in want.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 100352 // 8)
    for key, cut in config["reduced"].items():
        assert cut["published"] == want[key] and cut["here"] == config[key]
    if os.path.exists(CATALOG):  # the row itself, where the guide is at hand
        row = next(json.loads(line) for line in open(CATALOG) if '"granite-4.0-h-micro"' in line)
        assert row["config"] == want and row["source_url"] == config["source"]
    fields = cells.config_fields(config)
    assert fields["layer_types"] == want["layer_types"] and fields["num_layers"] == 10
    # what flash_roofline reads: the published 64-wide heads, whatever the kernel pads to
    assert (fields["num_heads"], fields["num_kv_heads"], fields["head_dim"]) == (32, 8, 64)
    assert (fields["ssm_num_heads"], fields["ssm_head_dim"], fields["ssm_state_dim"],
            fields["ssm_conv_kernel"]) == (64, 64, 128, 4)
    assert (fields["embedding_multiplier"], fields["residual_multiplier"],
            fields["attention_multiplier"], fields["logits_scaling"]) == (12, 0.22, 0.015625, 8)
    assert fields["tie_embeddings"] is True and fields["position_type"] == "none"
    # the guide's floors: a whole period and four layers, an eighth of the vocabulary
    run = want["layer_types"][:config["num_hidden_layers"]]
    assert run == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4 and run == want["layer_types"][10:20]
    assert config["vocab_size"] * 8 >= want["vocab_size"]
    for stated in ("deployment", "assumed", "not_modelled"):
        assert config[stated], stated
    assert {"initializer_range", "mamba_init", "time_step_limit", "head_dim"} <= set(config["assumed"])
    assert "vocab_tp 8" in config["deployment"] and "four pipeline stages" in config["deployment"]
    from galvatron_tpu.models import granite_hybrid

    assert config["source"] == granite_hybrid.GRANITE_4_H_MICRO_SOURCE
    preset = granite_hybrid.PUBLISHED["granite-4.0-h-micro"]
    assert all(preset[k] == v for k, v in want.items() if k in preset)
    assert set(want) - set(preset) == {"model_type"}
    assert config["initializer_range"] == granite_hybrid.INITIALIZER_RANGE


def test_the_program_built_from_the_file_counts_772_160_448_parameters():
    import jax
    import numpy as np

    from galvatron_tpu.models import base as M

    cell = cells.load_cell(REPO, CELL)
    cfg = cells.register_family(cell).config_fn(None, max_seq_len=4096)
    assert cfg.layer_kinds() == ("ssm.dense",) * 5 + ("dense",) + ("ssm.dense",) * 4
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert count == 772_160_448
    assert count * 16 / 2 ** 30 == pytest.approx(11.51, abs=0.01)  # GiB of state, of a chip's 15.75


def test_the_first_loss_is_derived_for_logits_divided_by_8():
    """`harness.expected_first_loss` adds hidden x init_std^2 / 2 = 0.4096 for
    unit-RMS rows against an N(0, 0.02^2) head; here the logits are divided by
    8, so that term is 64 times smaller, and `plus` is the (negative)
    difference with the tied table's measured part."""
    cell = cells.load_cell(REPO, CELL)
    first = cell.config["checks"]["first_loss"]
    scaled = 2048 * 0.02 ** 2 / 2 / 64
    assert scaled == pytest.approx(0.0064)
    assert first["plus"] < 0 and -0.4096 + scaled <= first["plus"] <= -0.4096 + scaled + 0.02
    assert harness.expected_first_loss(cell) == pytest.approx(
        math.log(12544) + 0.4096 + first["plus"], abs=1e-12)
    assert first["abs"] <= 0.1 and cell.config["checks"]["reference_loss"]["abs"] <= 2e-3
    assert "tied" in first["plus_why"] and "12" in first["plus_why"]


# ------------------------------------------------------------ hand arithmetic
def test_flops_a_token_by_hand():
    cell = cells.load_cell(REPO, CELL)
    f, c = cell.fields, costs()
    ssm = c.ssm_mixer_fwd_flops_a_token(f)
    assert ssm["projections"] == 2 * (2048 * 8512 + 4096 * 2048)
    assert ssm["core"] == 4 * 64 * 64 * 128  # two (d_head, d_state) products a head
    attention = c.attention_mixer_fwd_flops_a_token(f, 4096)
    assert attention["projections"] == 2 * (2 * 2048 * 2048 + 2 * 2048 * 512)
    assert attention["core"] == 2 * 4096 * 32 * (64 + 64) // 2  # q k^T and p v, the causal half
    mlp = c.mlp_fwd_flops_a_token(f)
    assert mlp == 3 * 2 * 2048 * 8192
    head = 2 * 2048 * 12544
    fwd = 9 * sum(ssm.values()) + sum(attention.values()) + 10 * mlp + head
    assert cells.flops_a_token(cell) == 3 * fwd == c.train_flops_a_token(f, 4096)
    assert cells.flops_a_token(cell) / 1e9 == pytest.approx(4.738, abs=5e-4)
    assert c.ssm_layers(f) == 9
    # a change of sequence length cannot move the state-space layers' count
    assert c.train_flops_a_token(f, 8192) - c.train_flops_a_token(f, 4096) == 3 * attention["core"]
    # the shares of the forward FLOPs (ISSUE 39's, and the cell's `why`)
    shares = {"MLPs": 10 * mlp, "Mamba mixers": 9 * sum(ssm.values()),
              "attention mixer": sum(attention.values()), "head": head}
    assert {k: round(100 * v / fwd) for k, v in shares.items()} == {
        "MLPs": 64, "Mamba mixers": 31, "attention mixer": 2, "head": 3}


def test_the_scans_floor_by_hand():
    f, c = cells.load_cell(REPO, CELL).fields, costs()
    fwd, bwd = c.ssd_cost(f, 4096, "fwd"), c.ssd_cost(f, 4096, "bwd")
    assert fwd["flops"] == 4 * 64 * 64 * 128 * 4096 and bwd["flops"] == 2 * fwd["flops"]
    xbc, y, dt = (64 * 64 + 2 * 128) * 2, 64 * 64 * 2, 64 * 4
    assert fwd["bytes"] == (xbc + dt + y) * 4096  # each operand in, the output out, once
    assert bwd["bytes"] == (xbc + dt + y + xbc + dt) * 4096  # those, dy, and the gradients
    # memory bound at the chip's peaks: 0.086 ms forward, 0.131 ms backward a layer
    assert flops.least_time_s(fwd, PEAK) == (fwd["bytes"] / 819e9, "memory")
    assert flops.least_time_s(bwd, PEAK)[0] * 1e3 == pytest.approx(0.1306, abs=1e-3)


# ------------------------------------------------------------------ readers
def label(instruction, op_name):
    return trace._label("%%%s = bf16[8] custom-call(...)" % instruction, {instruction: op_name})


def handmade(counters=True, ssm=True):
    """The cell's step as the compiled step labels it: three runs (5 scanned,
    the attention layer, 4 scanned), the program's scope names nested under
    the transforms' wrappers."""
    r0, r1, r2 = (tracing.layers_scope(k) for k in range(3))
    fwd = "jit(train_step)/jvp(%s)/while/body/closed_call/" % r0
    bwd = "jit(train_step)/transpose(jvp(%s))/while/body/closed_call/checkpoint/" % r2
    remat = bwd + "rematted_computation/"
    full = "jit(train_step)/jvp(%s)/" % r1
    ops = {
        label("fusion.20", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): [1e-3, 1],
        label("fusion.21", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): [5e-3, 1],
        label("flash_attention.7", full + "pallas_call"): [2e-3, 1],  # the attention layer's: flash_ms
        label("fusion.5", full + tracing.ATTN_PROJ + "/dot_general"): [1e-3, 1],
        label("fusion.6", full + tracing.MLP + "/dot_general"): [3e-3, 1],
        label("fusion.7", fwd + tracing.MLP + "/dot_general"): [10e-3, 5],
        label("fusion.8", remat + tracing.MLP + "/dot_general"): [6e-3, 4],
        label("fusion.9", bwd + tracing.MLP + "/dot_general"): [20e-3, 4],
        label("fusion.10", fwd + "mul"): [0.5e-3, 5],  # a run's self time
    }
    if ssm:
        ops.update({
            label("fusion.2", fwd + tracing.ATTN_SSM + "/dot_general"): [4e-3, 5],
            label("fusion.3", bwd + tracing.ATTN_SSM + "/dot_general"): [8e-3, 4],
            label("fusion.4", fwd + tracing.ATTN_SSD + "/while/body/closed_call/checkpoint/dot_general"): [2e-3, 20],
            label("fusion.11", remat + tracing.ATTN_SSD + "/while/body/closed_call/dot_general"): [2e-3, 16],
            label("fusion.12", bwd + tracing.ATTN_SSD + "/while/body/closed_call/checkpoint/dot_general"): [5e-3, 16],
        })
    events = [] if not counters else [
        {"type": "step", "iter": i, "loss": 9.44, "ssm_state_abs_max": 5.0 + i} for i in range(4)]
    return {"trace": {"ops_a_step": ops}, "peak": PEAK, "cell": cells.load_cell(REPO, CELL),
            "events": events, "window_steps": (0, 4)}


def test_the_readers_read_the_programs_scopes():
    run = handmade()
    assert read("ssm_mixer_ms", run) == pytest.approx(12.0)  # not the scan
    assert read("ssd_ms", run) == pytest.approx(9.0)  # forward, recomputed, backward
    # the two scopes are disjoint and add up to the state-space mixers
    assert read("ssm_mixer_ms", run) + read("ssd_ms", run) == pytest.approx(
        scopes.ms_a_step(run, r"gt\.attn\.ss[md]"))
    assert read("g4h_mlp_ms", run) == pytest.approx(39.0) == read("mlp_ms", run)
    assert read("ssm_state_abs_max", run) == pytest.approx(6.5)
    assert read("flash_ms", run) == pytest.approx(2.0)
    assert set(telemetry.SSM_STEP_FIELDS) == {"ssm_state_abs_max"}
    assert set(telemetry.SSM_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    assert (tracing.ATTN_SSM, tracing.ATTN_SSD) == ("gt.attn.ssm", "gt.attn.ssd")
    # the layer readers see the nested scopes as the layers', and the parts add up
    assert scopes.ms_a_step(run, scopes.LAYERS_REMAT) == pytest.approx(6.0 + 2.0)
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert parts["rest"] == pytest.approx(0.5) and parts[tracing.ATTN_SSD] == pytest.approx(9.0)
    assert sum(parts.values()) == pytest.approx(sum(
        scopes.ms_a_step(run, rx) for rx in (scopes.LAYERS_FWD, scopes.LAYERS_REMAT, scopes.LAYERS_BWD)))


def test_the_share_of_the_floor_by_hand_and_never_over_100():
    c, f = costs(), cells.load_cell(REPO, CELL).fields
    least = 9 * sum(flops.least_time_s(c.ssd_cost(f, 4096, w), PEAK)[0] for w in ("fwd", "bwd"))
    assert least * 1e3 == pytest.approx(1.947, abs=2e-3)
    run = handmade()
    assert read("ssd_roofline", run) == pytest.approx(100 * least / 9e-3)
    # any time the floor allows: one forward and one backward a layer at their least times read
    # 100, and a recomputed forward, which every run under --checkpoint 1 has, reads less
    for lab, value in run["trace"]["ops_a_step"].items():
        if "gt.attn.ssd" in lab:
            which = "bwd" if "transpose" in lab and "rematted" not in lab else "fwd"
            value[0] = 9 * flops.least_time_s(c.ssd_cost(f, 4096, which), PEAK)[0]
    with_remat = read("ssd_roofline", run)
    assert 50.0 < with_remat < 100.0
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if not ("gt.attn.ssd" in k and "rematted" in k)}
    assert read("ssd_roofline", run) == pytest.approx(100.0)


def test_a_program_without_the_scopes_or_the_counter_gives_nothing_to_read():
    """What the parent of this PR and the other cells hand the readers: None,
    not zero and not an error."""
    no_scopes = {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}
    for run in ({**handmade(False), "trace": None}, {**handmade(False), **no_scopes}):
        assert [read(name, run) for name in READERS] == [None] * len(READERS)
    dense_alone = handmade(counters=False, ssm=False)  # a program with the MLP's scope and no state-space layer
    for name in ("ssm_mixer_ms", "ssd_ms", "ssd_roofline", "ssm_state_abs_max"):
        assert read(name, dense_alone) is None
    assert read("g4h_mlp_ms", dense_alone) == pytest.approx(39.0)
    q3n = {**handmade(), "cell": cells.load_cell(REPO, "qwen3next-c1-s8k")}
    assert read("ssd_roofline", q3n) is None  # its FLOPs module has no ssd_cost
    dense_cell = {**handmade(), "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert read("ssd_roofline", dense_cell) is None  # its configuration names no `flops`


# ------------------------------------------------- the step recorded on the chip
@pytest.fixture(scope="module")
def recorded_run():
    reduced = trace.reduce(trace.load_events(os.path.join(FIXTURES, CELL + ".trace_events.json.gz")),
                           harness.STEP_NAMES)
    with open(os.path.join(FIXTURES, CELL + ".expected.json")) as f:
        expected = json.load(f)
    events = [{"type": "step", "iter": 0, "ssm_state_abs_max": expected["ssm_state_abs_max"]}]
    return {"trace": reduced, "peak": PEAK, "cell": cells.load_cell(REPO, CELL), "events": events,
            "window_steps": (0, 1)}, expected


def test_the_readers_on_the_step_recorded_on_the_chip(recorded_run):
    """Device 0's events of ONE step of the traced tail of a `--trace 2` run of
    the cell on a v5e (PR 39); the expected numbers are what the readers gave
    on that step when it was recorded, within a hundredth of what the run
    reported over its whole tail."""
    run, expected = recorded_run
    assert run["trace"]["steps"] == expected["steps"] == 1
    assert run["trace"]["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    for name in READERS + ("layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms", "layers_rest_ms",
                           "flash_ms", "flash_roofline", "unscoped_pct", "head_loss_ms", "attn_proj_ms"):
        assert read(name, run) == pytest.approx(expected[name], rel=1e-9), name
        assert read(name, run) == pytest.approx(expected["run_reported"][name], rel=0.02), name
    assert 0 < read("ssd_roofline", run) < 100 and 0 < read("flash_roofline", run) < 100
    # the kernels, not the XLA fallback: three flash custom calls of the one attention layer
    kernels = cells.load_module(REPO, "benchmarks/layer_metrics/flash_ms.py").per_kernel(run)
    assert {kind: calls for kind, (_, calls) in kernels.items()} == {"fwd": 2.0, "dkv": 1.0, "dq": 1.0}


def test_the_recorded_steps_parts_add_up(recorded_run):
    """flash + every nested scope + the runs' self time = forward +
    recomputation + backward, and with the top-level scopes and the unscoped
    ops the device's busy time: no op is counted twice or dropped."""
    run, _ = recorded_run
    parts = cells.load_module(REPO, "benchmarks/layer_metrics/layers_rest_ms.py").parts(run)
    assert set(parts) == {"flash", "rest", tracing.MLP, tracing.ATTN_PROJ, tracing.ATTN_SSM, tracing.ATTN_SSD}
    layers = sum(read("layers_%s_ms" % phase, run) for phase in ("fwd", "remat", "bwd"))
    assert sum(parts.values()) == pytest.approx(layers, abs=1e-6)
    assert parts[tracing.ATTN_SSD] == pytest.approx(read("ssd_ms", run), abs=1e-9)
    top = sum(read(name, run) for name in ("embed_ms", "head_loss_ms", "optimizer_ms", "guard_select_ms"))
    unscoped = scopes.ms_a_step(run, scopes.UNSCOPED)
    assert layers + top + unscoped == pytest.approx(run["trace"]["busy_s"] * 1e3, rel=1e-6)
    # no XLA rematerialisation op takes a hundredth of the step
    remat_named = sum(v[0] for k, v in run["trace"]["ops_a_step"].items() if ".remat" in k.split(":")[0])
    assert remat_named < 0.01 * run["trace"]["busy_s"]


# --------------------------------------------- the configuration from its files
@pytest.fixture
def root(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = cells.load_json(REPO, "benchmarks/configs/%s.json" % CONFIG)
    config.update(TINY)
    for key in config["reduced"]:
        config["reduced"][key]["here"] = TINY[key]
    # the same derivation at the tiny sizes: -(hidden x std^2 / 2) (1 - 1 / 64)
    config["checks"]["first_loss"]["plus"] = -(64 * 0.02 ** 2 / 2) * (1 - 1 / 64)
    (tmp_path / "benchmarks/configs/g4h-tiny.json").write_text(json.dumps(config))
    (tmp_path / "benchmarks/traffic/b2-s128-g4h.json").write_text(json.dumps({
        "why": "test", "global_batch": 2, "seq_length": 128, "chips": 1,
        "train_flags": ["--world_size", "1", "--checkpoint", "1"], "warmup_steps": 6}))
    manifest = cells.load_json(REPO, cells.MANIFEST)
    manifest["configs"].append({"name": "g4h-tiny", "source": "test", "why": "test",
                                "reduced": sorted(config["reduced"]),
                                "file": "benchmarks/configs/g4h-tiny.json"})
    manifest["workloads"].append({"name": "g4h-tiny-cell", "config": "g4h-tiny",
                                  "traffic": "b2-s128-g4h", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append("g4h-tiny-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)


def test_the_configuration_runs_from_its_files_at_a_tiny_size(root, tmp_path):
    """Configuration, reference, FLOPs module and checks are the committed
    files'; only the sizes are the test's. Everything but the TPU kernel
    check holds on the CPU: three runs of layers, one chunk of the scan a
    sequence, the tied head, the four multipliers."""
    from . import test_manifest

    test_manifest.check_cell_finds_its_files(root, "g4h-tiny-cell")
    test_manifest.check_reduced_in_the_manifest_is_reduced_in_the_file(root, "g4h-tiny")
    test_manifest.check_the_program_receives_the_published_keys(root, "g4h-tiny-cell")
    cell = cells.load_cell(root, "g4h-tiny-cell")
    lines = []
    result = harness.run_cell(cell, seed=2**31 + 39, seconds=0.5, traced=False, peaks=CPU_PEAK,
                              t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert {k for k, ok in detail["checks"].items() if not ok} == {"kernel_in_step"}
    assert abs(detail["first_loss"] - detail["reference_loss"]) < \
        cell.config["checks"]["reference_loss"]["abs"]
    assert detail["expected_first_loss"] == pytest.approx(math.log(512) + 64 * 0.02 ** 2 / 2 / 64, abs=1e-12)
    assert abs(detail["first_loss"] - detail["expected_first_loss"]) < 0.1
    assert detail["flops_a_token"] == costs().train_flops_a_token(cell.fields, 128)
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"}

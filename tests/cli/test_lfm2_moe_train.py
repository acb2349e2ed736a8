"""`python -m galvatron_tpu.cli train --model_type lfm2_moe` end to end on the
virtual CPU devices: the normal entry, step, scan over the three runs the first
five layers of the published pattern give (two conv + dense MLP, attention +
experts, two conv + experts: all three kinds), remat, guard, the router's bias
update and the optimizer, at a toy size set by the CLI's own manual-size flags
(the 32 experts with 4 a token, the dense width 7168, 3 taps and rope 1e6 stay
the preset's). The data is a corpus that counts (token t + 1 follows token t),
so that the steps after the warm-up's first show in the loss; the synthetic stream's uniform
tokens have nothing to learn."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.data.dataset import write_indexed_dataset
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "lfm2_moe", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_kv_heads", "2",
    "--ffn_hidden_size", "32", "--num_layers", "5", "--vocab_size", "128",
    "--seq_length", "48", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "1",
]


def run(extra, iters=4):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def counting(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("lfm2") / "counting")
    write_indexed_dataset(path, [[(start + i) % 128 for i in range(640)] for start in range(64)])
    return ["--data_path", path, "--split", "100,0,0"]


@pytest.fixture(scope="module")
def one_device(counting):
    return run(["--world_size", "1"] + counting)


def test_trains_on_one_device_and_the_loss_falls(one_device):
    losses = one_device["losses"]
    assert len(losses) == 4 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 with sigma^2 = 64 x 0.02^2 (the tied table's own part is far inside 0.1)
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.1)
    # the warm-up starts at a learning rate of 0: steps 0 and 1 see the same weights on two batches
    assert losses[1] == pytest.approx(losses[0], abs=0.02) and losses[3] < losses[2] < min(losses[:2]) - 0.3


def test_dp2_zero2_follows_one_device_and_logs_the_compile_counter(one_device, counting, tmp_path):
    tele = str(tmp_path / "lfm2.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele] + counting)
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 4
    for e in steps:
        assert e["expert_load_max_over_mean"] >= 1.0 and "loss_load_balance" not in e
        assert not set(T.LINEAR_STEP_FIELDS) & set(e) and "ssm_state_abs_max" not in e
    # the bias moves by the update rate a step, from the second step on
    assert [round(e["router_bias_abs_max"], 6) for e in steps] == [0.0, 0.001, 0.002, 0.003]
    # conv + dense twice, attention + experts, conv + experts twice, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 2), (1, 2, 3), (2, 3, 5)]
    compiles = [e for e in events if e["type"] == "compile"]
    took = [e["forms"] for e in compiles]
    assert [set(t[forms.SHORT_CONV]) for t in took] == [{"xla"}]  # the conv layers' mixers were traced, in their one form
    assert [set(t[forms.MOE_ROWS]) for t in took] == [{"xla"}]  # off a TPU the rows move by XLA's gathers
    assert all(forms.KDA_RULE not in t and forms.DELTA_RULE not in t for t in took)
    assert all(forms.EXPERT_WINDOW not in t for t in took)  # all 32 experts are held: no window to speak of
    assert [forms.GATED_KERNEL_GRADS in t for t in took] == [False]  # off a TPU the compiler lays the gradients out


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--global_tp_deg", "2", "--sequence-parallel"],
    ["--world_size", "5", "--pp_deg", "5", "--chunks", "5", "--global_train_batch_size", "5"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "1", "--autotune", "observe"],
], ids=["tp2", "sp", "pp5", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_conv_layers_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert "short-convolution" in str(e.value)


def test_a_share_of_the_experts_reports_its_window(tmp_path):
    """With 4 of the 32 experts held (what only a registered family sets: the
    CLI has no flag for it), 2 x 512 tokens x 4 choices are 4096 assignments,
    the window over the held experts' rows is 1.5 x 512 of them in 512-row
    tiles and a tile, and the `compile` event says so; the `step` events count
    the routed blocks (of three) whose held rows outgrew it, beside the rows."""
    from galvatron_tpu.models.lfm2_moe import lfm2_moe_config
    from galvatron_tpu.models.registry import ModelFamily, register

    register(ModelFamily(
        name="lfm2_moe_share", default_size="lfm2-8b-a1b", meta_configs={"lfm2-8b-a1b": {}},
        config_fn=lambda size, **overrides: lfm2_moe_config(size, experts_held=4, experts_held_start=8, **overrides)))
    tele = str(tmp_path / "share.jsonl")
    argv = [flag for flag in TINY]
    argv[argv.index("lfm2_moe")] = "lfm2_moe_share"
    argv[argv.index("--seq_length") + 1] = "512"
    train(initialize_galvatron(mode="train_dist", argv=argv + [
        "--train_iters", "2", "--world_size", "1", "--telemetry", tele]))
    events, errors = T.read_events(tele)
    assert errors == []
    assert [set(e["forms"][forms.EXPERT_WINDOW]) for e in events if e["type"] == "compile"] == [{"1536"}]
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 2
    for e in steps:
        assert 0 <= e["expert_window_fallbacks"] <= 3 and e["expert_window_fallbacks"] == int(e["expert_window_fallbacks"])
        assert e["expert_rows_held"] > 0

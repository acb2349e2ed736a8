"""`python -m galvatron_tpu.cli train --model_type laguna` end to end on the
virtual CPU devices: the normal entry, step, scan over the three runs the first
five layers of the published pattern give (full attention + dense MLP, window
attention + experts three times, full attention + experts: all three kinds),
remat, guard and the optimizer, at a toy size set by the CLI's own manual-size
flags (the 64 heads of a window layer, head_dim 128, the window of 512, yarn, the
256 experts with 8 a token beside a shared one and the dense width 8192 stay
the preset's). The data is a corpus that counts (token t + 1 follows token t),
so that the steps after the warm-up's first show in the loss; the synthetic
stream's uniform tokens have nothing to learn."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.data.dataset import write_indexed_dataset
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "laguna", "--set_model_config_manually", "1",
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
    path = str(tmp_path_factory.mktemp("laguna") / "counting")
    write_indexed_dataset(path, [[(start + i) % 128 for i in range(640)] for start in range(64)])
    return ["--data_path", path, "--split", "100,0,0"]


@pytest.fixture(scope="module")
def one_device(counting):
    return run(["--world_size", "1"] + counting)


def test_trains_on_one_device_and_the_loss_falls(one_device):
    losses = one_device["losses"]
    assert len(losses) == 4 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 with sigma^2 = 64 x 0.02^2: an untied head on unit-RMS rows
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.1)
    # the warm-up starts at a learning rate of 0: steps 0 and 1 see the same weights on two batches of 96 tokens
    # (4.892, 4.847), and the two steps after them have learnt (4.881, 4.722: 64 window heads of 128 on a hidden
    # size of 64 learn slowly, which is the toy's shape and not the model's)
    assert losses[1] == pytest.approx(losses[0], abs=0.1) and np.mean(losses[2:]) < np.mean(losses[:2]) - 0.03


def test_dp2_zero2_follows_one_device_and_logs_the_compile_counter(one_device, counting, tmp_path):
    tele = str(tmp_path / "laguna.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele] + counting)
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 4
    for e in steps:
        assert e["expert_load_max_over_mean"] >= 1.0 and "router_bias_abs_max" not in e
        assert not set(T.LINEAR_STEP_FIELDS) & set(e) and "ssm_state_abs_max" not in e
    # full + dense, window + experts three times (one scanned run), full + experts, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 1), (1, 1, 4), (2, 4, 5)]
    compiles = [e for e in events if e["type"] == "compile"]
    took = [e["forms"] for e in compiles]
    assert [set(t[forms.WINDOW_ATTENTION]) for t in took] == [{"xla"}]  # off a TPU the band is a mask on XLA's logits
    assert [forms.WINDOW_OPERANDS in t for t in took] == [False]  # so nothing reads q as projected
    assert [set(t[forms.MOE_ROWS]) for t in took] == [{"xla"}]  # and the rows move by XLA's gathers
    assert all(forms.KDA_RULE not in t and forms.DELTA_RULE not in t and forms.SHORT_CONV not in t for t in took)
    assert all(forms.EXPERT_WINDOW not in t for t in took)  # all 256 experts are held: no window of rows
    assert [forms.GATED_KERNEL_GRADS in t for t in took] == [False]  # off a TPU the compiler lays the gradients out


def test_a_model_without_window_layers_logs_no_window_counter(counting, tmp_path):
    tele = str(tmp_path / "llama.jsonl")
    train(initialize_galvatron(mode="train_dist", argv=[
        "--model_type", "llama", "--set_model_config_manually", "1", "--hidden_size", "64",
        "--num_attention_heads", "4", "--ffn_hidden_size", "96", "--num_layers", "2", "--vocab_size", "128",
        "--seq_length", "48", "--mixed_precision", "fp32", "--global_train_batch_size", "2", "--train_iters", "1",
        "--world_size", "1", "--telemetry", tele] + counting))
    compiles = [e for e in T.read_events(tele)[0] if e["type"] == "compile"]
    assert len(compiles) == 1 and forms.WINDOW_ATTENTION not in compiles[0]["forms"]
    assert forms.WINDOW_OPERANDS not in compiles[0]["forms"]


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--global_tp_deg", "2", "--sequence-parallel"],
    ["--world_size", "5", "--pp_deg", "5", "--chunks", "5", "--global_train_batch_size", "5"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "1", "--autotune", "observe"],
], ids=["tp2", "sp", "pp5", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_window_layers_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags, iters=1)
    assert "window attention layer" in str(e.value)

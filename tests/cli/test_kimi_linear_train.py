"""`python -m galvatron_tpu.cli train --model_type kimi_linear` end to end on the
virtual CPU devices: the normal entry, step, scan over the four runs the first
five layers of the published pattern give (KDA + dense MLP, two KDA + experts,
MLA + experts, KDA + experts), remat, guard, the router's bias update and the
optimizer, at a small size set by the CLI's own manual-size flags (the KDA
heads, 32 of 128, the latent ranks and head dims and the 256 experts stay the
preset's, so a CPU step takes seconds). The data is a corpus that counts (token
t + 1 follows token t), so that two steps of training show in the loss; the
synthetic stream's uniform tokens have nothing to learn."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.data.dataset import write_indexed_dataset
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "kimi_linear", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "2",
    "--ffn_hidden_size", "32", "--num_layers", "5", "--vocab_size", "128",
    "--seq_length", "64", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "1",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def counting(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("kimi") / "counting")
    write_indexed_dataset(path, [[(start + i) % 128 for i in range(640)] for start in range(64)])
    return ["--data_path", path, "--split", "100,0,0"]


@pytest.fixture(scope="module")
def one_device(counting):
    return run(["--world_size", "1"] + counting)


def test_trains_on_one_device_and_the_loss_falls(one_device):
    losses = one_device["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 with sigma^2 = 64 x 0.02^2: the objective has no other term
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2, abs=0.1)
    assert losses[2] < losses[1] < losses[0]


def test_dp2_zero2_follows_one_device_and_reports_its_counters(one_device, counting, tmp_path):
    tele = str(tmp_path / "kimi.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele] + counting)
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.LINEAR_STEP_FIELDS) <= set(e) and e["linear_state_abs_max"] > 0.0
        assert 0.0 < e["linear_decay_mean"] < 1.0 and "ssm_state_abs_max" not in e
        assert e["expert_load_max_over_mean"] >= 1.0 and "loss_load_balance" not in e
    # the bias moves by the update rate a step, from the second step on
    assert [round(e["router_bias_abs_max"], 6) for e in steps] == [0.0, 0.001, 0.002]
    # KDA + dense, KDA + experts twice, MLA + experts, KDA + experts, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 1), (1, 1, 3), (2, 3, 4), (3, 4, 5)]
    # the scalar rule's kernels are not this model's: the compile report says nothing of them
    assert all(forms.DELTA_RULE not in e["forms"] for e in events if e["type"] == "compile")
    # its own are: off a TPU none of the four KDA layers takes `kda_fwd` / `kda_bwd`, and the report says so
    assert [set(e["forms"][forms.KDA_RULE]) for e in events if e["type"] == "compile"] == [{"xla"}]
    assert [{form for part in (forms.KDA_CONV_NORM, forms.KDA_GATE, forms.KDA_GATED_NORM) for form in e["forms"][part]}
            for e in events if e["type"] == "compile"] == [{"xla"}]  # nor the passes around it


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--global_tp_deg", "2", "--sequence-parallel"],
    ["--world_size", "5", "--pp_deg", "5", "--chunks", "5", "--global_train_batch_size", "5"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "1", "--autotune", "observe"],
], ids=["tp2", "sp", "pp5", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_kda_layers_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert "Kimi-Delta-Attention" in str(e.value)

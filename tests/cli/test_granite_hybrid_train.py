"""`python -m galvatron_tpu.cli train --model_type granite_hybrid` end to end
on the virtual CPU devices: the normal entry, step, scan over the three runs a
listed pattern gives (five Mamba-2 layers, the attention layer, four Mamba-2
layers), remat, guard and optimizer, at a small size set by the CLI's own
manual-size flags (the Mamba heads, their states, the taps and the four
multipliers stay the preset's: 64 heads of 64 with states of 128)."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "granite_hybrid", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_kv_heads", "2",
    "--ffn_hidden_size", "96", "--num_layers", "10", "--vocab_size", "128",
    "--seq_length", "64", "--mixed_precision", "fp32", "--global_train_batch_size", "2",
    "--lr", "1e-3", "--checkpoint", "1", "--lr_warmup_iters", "2",
]


def run(extra, iters=3):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def one_device():
    return run(["--world_size", "1"])


def test_trains_on_one_device(one_device):
    losses = one_device["losses"]
    assert len(losses) == 3 and np.isfinite(losses).all()
    # ln 128 + sigma^2 / 2 with the logits divided by 8: sigma^2 = 64 x 0.02^2 / 64
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2 / 64, abs=0.05)


def test_dp2_zero2_follows_one_device_and_reports_its_counter(one_device, tmp_path):
    tele = str(tmp_path / "g4h.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele])
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.SSM_STEP_FIELDS) <= set(e) and e["ssm_state_abs_max"] > 0.0
        assert not (set(T.EXPERT_STEP_FIELDS) - {"loss_ce"}) & set(e) and "linear_decay_mean" not in e
    # five Mamba-2 layers, the attention layer, four Mamba-2 layers, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 5), (1, 5, 6), (2, 6, 10)]
    # no linear layer: the compile report has nothing to say of the delta rule's kernels
    assert all(forms.DELTA_RULE not in e["forms"] for e in events if e["type"] == "compile")
    # ten gated kernels, and off a TPU none is read through `grad_as_stored` (models/base.run_layers)
    assert [forms.GATED_KERNEL_GRADS in e["forms"] for e in events if e["type"] == "compile"] == [False]


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--pp_deg", "2", "--chunks", "2"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "1", "--autotune", "observe"],
], ids=["tp2", "pp2", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_state_space_layers_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018") as e:
        run(flags)
    assert "state-space" in str(e.value)

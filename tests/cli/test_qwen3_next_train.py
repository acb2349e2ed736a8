"""`python -m galvatron_tpu.cli train --model_type qwen3_next` end to end on
the virtual CPU devices: the normal entry, step, scan over a run of linear
layers and a run of one attention layer, remat, guard and optimizer, at a
small size set by the CLI's own manual-size flags (the linear
heads and their dims, the head_dim of 256, the experts' count and the experts
a token stay the preset's: 512 experts of width 32 with 10 a token)."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import forms
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "qwen3_next", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "1",
    "--ffn_hidden_size", "32", "--num_layers", "4", "--vocab_size", "128",
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
    # ln 128 + sigma^2 / 2 on untrained weights, and 0.001 x a load-balancing loss of about 10
    assert losses[0] == pytest.approx(np.log(128) + 64 * 0.02 ** 2 / 2 + 0.01, abs=0.15)


def test_dp2_zero2_follows_one_device_and_reports_its_counters(one_device, tmp_path):
    tele = str(tmp_path / "q3n.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele])
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 3
    for e in steps:
        assert set(T.EXPERT_STEP_FIELDS) | set(T.LINEAR_STEP_FIELDS) <= set(e)
        assert not {"loss_mtp", "router_bias_abs_max", "expert_rows_held"} & set(e)
        assert e["loss"] == pytest.approx(e["loss_ce"] + 0.001 * e["loss_load_balance"], abs=1e-5)
        assert 0.0 < e["linear_decay_mean"] < 1.0 and e["linear_state_abs_max"] > 0.0
    # a run of three linear layers and a run of the attention layer, numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 3), (1, 3, 4)]
    # off a TPU none of the three linear layers takes the Pallas kernels, and the compile report says so
    assert [set(e["forms"][forms.DELTA_RULE]) for e in events if e["type"] == "compile"] == [{"xla"}]
    assert [set(e["forms"][forms.CONV_NORM]) | set(e["forms"][forms.GATED_NORM])
            for e in events if e["type"] == "compile"] == [{"xla"}]  # nor the passes around it
    assert all(forms.KDA_RULE not in e["forms"] and forms.KDA_CONV_NORM not in e["forms"]
               for e in events if e["type"] == "compile")  # no KDA layer: nothing said
    assert [set(e["forms"][forms.MOE_ROWS]) for e in events if e["type"] == "compile"] == [{"xla"}]  # nor do the four routed blocks' rows move by DMA


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--pp_deg", "2", "--chunks", "2"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "1", "--autotune", "observe"],
], ids=["tp2", "pp2", "cp2", "autotune"])
def test_the_driver_refuses_what_has_no_form_of_the_linear_layers_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018"):
        run(flags)

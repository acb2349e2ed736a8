"""`python -m galvatron_tpu.cli train --model_type glm4_moe_lite` end to end on
the virtual CPU devices: the normal entry, step, scan over two kinds of layer,
remat, guard, optimizer and checkpoint, at a small size set by the CLI's own
manual-size flags (latent attention's ranks and head dims, the dense width,
the experts' count and the experts a token stay the preset's)."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "glm4_moe_lite", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "2", "--num_kv_heads", "2",
    "--ffn_hidden_size", "32", "--num_layers", "3", "--vocab_size", "128",
    "--seq_length", "32", "--mixed_precision", "fp32", "--global_train_batch_size", "4",
    "--lr", "1e-3", "--checkpoint", "1",
]


def run(extra, iters=4):
    return train(initialize_galvatron(
        mode="train_dist", argv=TINY + ["--train_iters", str(iters)] + extra))


@pytest.fixture(scope="module")
def one_device():
    return run(["--world_size", "1"])


def test_trains_on_one_device(one_device):
    losses = one_device["losses"]
    assert len(losses) == 4 and np.isfinite(losses).all()
    # (1 + 0.3) x ln 128 on untrained weights: cross entropy and 0.3 x MTP's
    assert losses[0] == pytest.approx(1.3 * (np.log(128) + 64 * 0.02 ** 2 / 2), abs=0.15)


def test_dp2_zero2_follows_one_device_and_reports_its_counters(one_device, tmp_path):
    tele = str(tmp_path / "glm.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele])
    # the same step to rounding; from the third on a near-tied pick of 4 among
    # 64 flips under another order of summation (measured 3e-5 of the loss)
    np.testing.assert_allclose(s["losses"][:2], one_device["losses"][:2], rtol=1e-5)
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=2e-4)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 4
    for i, e in enumerate(steps):
        assert {"loss_ce", "loss_mtp", "expert_load_max_over_mean", "router_bias_abs_max"} <= set(e)
        assert not {"loss_load_balance", "loss_router_z", "expert_rows_held"} & set(e)
        assert e["loss"] == pytest.approx(e["loss_ce"] + 0.3 * e["loss_mtp"], abs=1e-5)
        # the bias this step read: 0.001 a step, from zero
        assert e["router_bias_abs_max"] == pytest.approx(0.001 * i, abs=1e-7)
    # a dense run and a routed one (and the head's pseudo-run), numbered as gt.layers.r<k>
    runs = [e for e in events if e["type"] == "layer_run" and e["run"] >= 0]
    assert [(e["run"], e["start"], e["stop"]) for e in runs] == [(0, 0, 1), (1, 1, 3)]


def test_a_checkpoint_carries_the_bias_and_an_optimizer_state_without_it(one_device, tmp_path):
    ckpt = str(tmp_path / "ckpt")
    first = run(["--world_size", "1", "--save", ckpt], iters=2)
    resumed = run(["--world_size", "1", "--load", ckpt], iters=4)
    np.testing.assert_allclose(first["losses"] + resumed["losses"], one_device["losses"], rtol=1e-6)


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "3", "--pp_deg", "3", "--chunks", "2"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "2", "--tp_comm_mode", "overlap"],
], ids=["tp2", "pp3", "cp2", "tp_comm_overlap"])
def test_the_driver_refuses_a_layout_with_no_expert_or_latent_form_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018"):
        run(flags)

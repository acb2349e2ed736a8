"""`python -m galvatron_tpu.cli train --model_type olmoe` end to end on the
virtual CPU devices: the normal entry, step, scan, remat, guard and optimizer,
at a small size set by the CLI's own manual-size flags (the experts' count and
the experts a token stay the preset's 64 and 8)."""

import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.obs import telemetry as T

TINY = [
    "--model_type", "olmoe", "--model_size", "olmoe-1b-7b", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_kv_heads", "4",
    "--ffn_hidden_size", "32", "--num_layers", "2", "--vocab_size", "128",
    "--seq_length", "32", "--mixed_precision", "fp32", "--global_train_batch_size", "4",
    "--train_iters", "4", "--lr", "1e-3", "--checkpoint", "1",
]


def run(extra):
    return train(initialize_galvatron(mode="train_dist", argv=TINY + extra))


@pytest.fixture(scope="module")
def one_device():
    return run(["--world_size", "1"])


def test_trains_on_one_device(one_device):
    losses = one_device["losses"]
    assert len(losses) == 4 and np.isfinite(losses).all()
    # ln 128 + 0.01 x 8 + 0.001 x (ln 64)^2 on untrained weights
    assert losses[0] == pytest.approx(np.log(128) + 0.097, abs=0.15)


def test_dp2_zero2_follows_one_device_and_reports_the_loss_by_parts(one_device, tmp_path):
    tele = str(tmp_path / "olmoe.jsonl")
    s = run(["--world_size", "2", "--default_dp_type", "zero2", "--telemetry", tele])
    np.testing.assert_allclose(s["losses"], one_device["losses"], rtol=1e-5)
    events, errors = T.read_events(tele)
    assert errors == []
    steps = [e for e in events if e["type"] == "step"]
    assert len(steps) == 4
    for e in steps:
        assert set(T.EXPERT_STEP_FIELDS) <= set(e)
        assert e["loss"] == pytest.approx(
            e["loss_ce"] + 0.01 * e["loss_load_balance"] + 0.001 * e["loss_router_z"], abs=1e-5)
        assert 1.0 <= e["expert_load_max_over_mean"] <= 8.0


def test_a_dense_runs_step_events_carry_no_expert_fields(tmp_path):
    tele = str(tmp_path / "dense.jsonl")
    argv = [a if a != "olmoe" else "llama" for a in TINY if a not in ("--model_size", "olmoe-1b-7b")]
    train(initialize_galvatron(mode="train_dist", argv=argv + ["--world_size", "1", "--telemetry", tele]))
    steps = [e for e in T.read_events(tele)[0] if e["type"] == "step"]
    assert steps and not any(set(T.EXPERT_STEP_FIELDS) & set(e) for e in steps)


@pytest.mark.parametrize("flags", [
    ["--world_size", "2", "--global_tp_deg", "2"],
    ["--world_size", "2", "--pp_deg", "2", "--chunks", "2"],
    ["--world_size", "2", "--global_cp_deg", "2"],
    ["--world_size", "2", "--tp_comm_mode", "overlap"],
], ids=["tp2", "pp2", "cp2", "tp_comm_overlap"])
def test_the_driver_refuses_a_layout_with_no_expert_form_before_tracing(flags):
    with pytest.raises(DiagnosticError, match="GLS018"):
        run(flags)
